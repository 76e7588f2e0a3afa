"""paddle.nn.functional namespace. Parity: python/paddle/nn/functional/."""
from .activation import *  # noqa: F401,F403
from .common import *  # noqa: F401,F403
from .conv import *  # noqa: F401,F403
from .norm import *  # noqa: F401,F403
from .loss import *  # noqa: F401,F403
from .pooling import *  # noqa: F401,F403
from .attention import *  # noqa: F401,F403
from .linear_attention import *  # noqa: F401,F403
