"""tick_device_us: device busy time in the window, from the profiler
trace, per lane-tick simulated (dynamics lanes x ticks of every request)."""


def read(record):
    trace = record["trace"]
    lane_ticks = sum(r["lanes"] * r["ticks"] for r in record["requests"])
    if trace is None or not lane_ticks:
        return None
    return 1e6 * trace["busy_s"] / lane_ticks
