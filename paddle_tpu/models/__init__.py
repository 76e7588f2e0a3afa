"""Model zoo: the five BASELINE config families, Qwen3-Next, SDAR, Kimi
Linear and JoyAI-LLM-Flash."""
from . import gpt
from . import bert
from . import llama
from . import vit
from . import moe
from . import qwen3_next
from . import sdar
from . import kimi_linear
from . import joyai_flash
