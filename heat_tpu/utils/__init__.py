"""Utilities (reference: /root/reference/heat/utils/). ``checkpoint`` is a
TPU-native addition: sharding-aware training-state persistence (the
reference has no model checkpointing — SURVEY §5)."""

from . import checkpoint
from . import compile_cache
from . import monitor
from . import data
from . import vision_transforms
from .checkpoint import load_checkpoint, save_checkpoint
from .compile_cache import place_compile_cache
