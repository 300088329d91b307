"""pack_share: host packing (``core/scenarios.pack_specs``) as a share of
the window, from the repository tracer's ``pack_specs`` spans."""


def read(record):
    if not record["spans"]:
        return None
    pack_s = sum(s["dur"] for s in record["spans"]
                 if s["name"] == "pack_specs") / 1e6
    return 100.0 * pack_s / record["window_s"]
