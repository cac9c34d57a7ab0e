"""``ht.program.miss`` spans in the traced window: lookups of a program
builder's ``lru_cache`` that built. Expected 0 (an eviction shows here and
not in ``compiles_in_window`` while JAX's own cache still serves the
program). Layer: program cache."""

from benchmarks import spans as S
from benchmarks import trace as T


def reduce(events, run):
    own, win = S.program_spans(events), T.window(events)
    if not own or win is None:
        return None
    return sum(1 for s in own if s.name == S.MISS and win[0] <= s.start_ns < win[1])
