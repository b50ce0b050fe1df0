"""Engine: mean time per committed decode step from the chunk's start to
the decode, slice and argmax programs dispatched (spans ``engine.prep`` and
``engine.dispatch`` on the scheduler's tracer), over the chunks that
committed a step in the traced window and admitted nothing. Moves
``itl_p50_ms``."""
import hostspans


def read(rec):
    return hostspans.per_step_ms(rec, ("engine.prep", "engine.dispatch"))
