"""fold_share: host fold and billing (``batched._lane_result``,
``cloud.bills_from_monthly_totals``) as a share of the window: each
request's wall time less its ``pack_specs`` and ``simulate_packed`` spans."""


def read(record):
    if not record["spans"]:
        return None
    inner_s = sum(s["dur"] for s in record["spans"]
                  if s["name"] in ("pack_specs", "simulate_packed")) / 1e6
    wall_s = sum(r["t1"] - r["t0"] for r in record["requests"])
    return 100.0 * (wall_s - inner_s) / record["window_s"]
