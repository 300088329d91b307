"""lane_days_per_s: simulated lane-days of every request completed in the
window (dynamics lanes x horizon in days) over the window's wall time."""


def read(record):
    lane_days = sum(r["lanes"] * r["days"] for r in record["requests"]
                    if r["ok"])
    return lane_days / record["window_s"]
