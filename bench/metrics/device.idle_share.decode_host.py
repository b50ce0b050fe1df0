"""Device: the share of the traced span in which no program runs on the
chip while the decode job is on its slot or waiting out its requeue: the
host path of the decode loop holding the chip idle. The job's runs and
waits come from the scheduler's tracer, put on the device's clock by the
clock anchors and the runtime's launches (``xplane.clock``). Moves
``itl_p50_ms``."""
import hostspans


def read(rec):
    return hostspans.decode_host_idle_share(rec)
