"""Share of the window's routed decode chunks that the online tuner spent
exploring a candidate k (router counters), in percent."""


def read(run):
    s = run.router_stats
    calls = s["explores"] + s["exploits"]
    return 100.0 * s["explores"] / calls if calls else None
