"""launch_row_use.lookup: rows requested over rows launched in the window,
in percent (``stats["rows"]`` over ``stats["launched_rows"]``). A launch
gathers ``coalesce x bucket`` rows, every lane counted, so the rest is
bucket padding and surplus lanes: gather work and device-to-host bytes
that serve no row. Nothing when the program does not count launched
rows."""


def read(obs):
    d = obs.stats_delta
    if not d.get("launched_rows"):
        return None
    return 100.0 * d["rows"] / d["launched_rows"]
