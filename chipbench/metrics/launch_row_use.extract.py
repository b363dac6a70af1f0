"""launch_row_use.extract: rows served over rows launched in the window,
in percent (``stats["rows"]`` over ``stats["launched_rows"]``). A where
launch gathers ``lanes x lane`` rows; what a request's last lane does not
fill, and surplus lanes, are gather work and device-to-host bytes that
serve no row. Nothing when the program does not count launched rows."""


def read(obs):
    d = obs.stats_delta
    if not d.get("launched_rows"):
        return None
    return 100.0 * d["rows"] / d["launched_rows"]
