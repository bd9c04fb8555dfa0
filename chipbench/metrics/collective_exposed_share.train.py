"""Share of the traced window in which a collective (the gradient and
weight exchange between chips) runs on a chip while no other op does,
averaged over the chips (device trace).  Nothing to read where the trace
holds no collective."""


def read(layer):
    red = layer.get("trace")
    if layer.get("kind") != "train" or not red or not red["devices"]:
        return None
    devs = red["devices"].values()
    if not any(v["collective_s"] > 0 for v in devs):
        return None
    exposed = sum(v["collective_exposed_s"] for v in devs) / len(devs)
    return 100.0 * exposed / red["window_s"]
