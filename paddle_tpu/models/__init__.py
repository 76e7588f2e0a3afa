"""Model zoo: the five BASELINE config families, and Qwen3-Next."""
from . import gpt
from . import bert
from . import llama
from . import vit
from . import moe
from . import qwen3_next
