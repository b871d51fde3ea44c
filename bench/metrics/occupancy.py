"""Scheduler occupancy: lanes busy (decoding or prefilling a chunk) over
lanes x scheduler iterations inside the window, from the engine's serving
hooks.  Moves the output token rate."""


def read(run):
    c = run["counts"]
    if not c.get("iterations"):
        return None
    return 100.0 * c["occupied_lane_iterations"] / (c["iterations"] * c["lanes"])
