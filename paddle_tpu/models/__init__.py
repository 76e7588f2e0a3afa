"""Model zoo: the five BASELINE config families, Qwen3-Next and SDAR."""
from . import gpt
from . import bert
from . import llama
from . import vit
from . import moe
from . import qwen3_next
from . import sdar
