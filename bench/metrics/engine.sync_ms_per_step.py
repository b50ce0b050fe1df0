"""Engine: mean time per committed decode step blocked reading the next
tokens to the host (span ``engine.sync``), over the chunks that committed
a step in the traced window and admitted nothing. Moves ``itl_p50_ms``."""
import hostspans


def read(rec):
    return hostspans.per_step_ms(rec, ("engine.sync",))
