"""Share of the window the training loop spent waiting in the loader's
``next()`` (host clock, around the program's ``iter_chunks``)."""


def read(layer):
    if layer.get("kind") != "train" or not layer.get("window_s"):
        return None
    return 100.0 * layer["loader_wait_s"] / layer["window_s"]
