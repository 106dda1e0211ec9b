"""Observability utilities: metric averaging, phase timers, graph viz."""

from .metrics import Performance
from .timers import Timers
from .viz import dump_net_json

__all__ = [
    "Performance",
    "Timers",
    "dump_net_json",
]
