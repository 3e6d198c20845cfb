"""Share of the traced window in which no operation ran on the device."""
LAYER = "device"
UNIT = "%"
MOVES = "output_tok_s"


def read(v):
    if not v.trace or not v.trace["window_s"]:
        return None
    return 100.0 * (1.0 - v.trace["busy_s"] / v.trace["window_s"])
