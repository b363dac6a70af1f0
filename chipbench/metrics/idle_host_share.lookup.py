"""idle_host_share.lookup: the chip's idle time in the traced window that
no ``pump.wait`` span covers, over the window (``DeviceTrace.window_s``),
in percent. The pump sits in ``pump.wait`` when it has nothing to launch
or retire, so chip idle outside it is held up by the host: the pump
taking and dispatching the next launch (``pump.launch``), fetching and
retiring the last one (``pump.retire``, ``pump.fetch``), or the machine
standing still. Chip idle is the gaps between the first chip's merged op
intervals (``chipbench/spans.py``). Nothing when the program writes no
pump spans."""
from chipbench import spans

CELL = "criteo-lookup"


def read(obs):
    t = spans.for_run(obs, __file__, CELL, "pump.launch")
    if t is None or not t.chips:
        return None
    idle = t.idle_ns() - spans.covered(t.idle, t.named("pump.wait"))
    return 100.0 * idle * 1e-9 / obs.trace.window_s
