"""Share of the traced window in which no op runs on a chip, averaged
over the cell's chips (device trace)."""


def read(layer):
    red = layer.get("trace")
    if layer.get("kind") != "train" or not red or not red["devices"]:
        return None
    idle = [v["idle_share"] for v in red["devices"].values()]
    return 100.0 * sum(idle) / len(idle)
