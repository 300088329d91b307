"""gate_passes: passes of the cloud admission gate (the batched engine's
``gcs_gate_passes`` counter, one prefix-sum pass over the bucket's
candidates each) per lane-tick, over the results of the requests that
came back. An unlimited bucket takes one pass on a tick with candidates,
no bucket none. ``None`` where the program keeps no such counter."""


def read(record):
    passes = lane_ticks = 0
    for req in record["requests"]:
        if not req["ok"]:
            continue
        for res in req["results"]:
            counters = getattr(res, "counters", None) or {}
            if "gcs_gate_passes" not in counters:
                return None
            passes += counters["gcs_gate_passes"]
            lane_ticks += req["ticks"]
    return passes / lane_ticks if lane_ticks else None
