"""Process start to the window's opening: corpus generation, the store
build, compiles or cache loads, and warm-up to a steady slab cache."""


def read(w):
    return w["setup_s"]
