"""idle_fetch_share.lookup: the chip's idle time in the traced window that
``pump.fetch`` spans cover, over the window (``DeviceTrace.window_s``),
in percent: the chip stands idle while the pump waits for a launch's
buffer and copies it to the host (``np.asarray`` on the launch's whole
``coalesce x bucket`` rows). Chip idle is the gaps between the first
chip's merged op intervals (``chipbench/spans.py``). Nothing when the
program writes no pump spans."""
from chipbench import spans

CELL = "criteo-lookup"


def read(obs):
    t = spans.for_run(obs, __file__, CELL, "pump.launch")
    if t is None or not t.chips:
        return None
    fetch = spans.covered(t.idle, t.named("pump.fetch"))
    return 100.0 * fetch * 1e-9 / obs.trace.window_s
