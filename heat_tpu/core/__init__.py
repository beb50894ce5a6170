"""Core of the TPU-native distributed tensor framework.

Mirrors the reference's flat re-export layout (heat/core/__init__.py:5-32):
everything is importable as ``heat_tpu.<name>``.
"""

from .communication import *
from . import communication
from .devices import *
from . import devices
from . import types
from .types import *
from . import version
from .version import __version__
from .constants import *
from .base import *
from .stride_tricks import *
from . import telemetry
from . import resilience
from .resilience import errstate
from . import memledger
from . import health_runtime
from . import tracelens
from . import numlens
from . import fusion
from . import elastic
from . import serving
from . import opsplane
from .dndarray import *
from .factories import *
from .memory import *
from .sanitation import *
from .arithmetics import *
from .relational import *
from .logical import *
from .rounding import *
from .exponential import *
from .trigonometrics import *
from .complex_math import *
from .printing import *
from .statistics import *
from .io import *
from . import io
from .manipulations import *
from .tiling import *
from . import tiling
from .indexing import *
from .signal import *
from . import random
from . import linalg
from .linalg import *
