"""Engine: mean time per committed decode step from the merge under the
engine lock to the chunk's return (span ``engine.merge``), over the chunks
that committed a step in the traced window and admitted nothing. Moves
``itl_p50_ms``."""
import hostspans


def read(rec):
    return hostspans.per_step_ms(rec, ("engine.merge",))
