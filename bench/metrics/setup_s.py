"""setup_s: process start to the opening of the measured window -- imports,
making the data on the device, compiling or loading every program, and the
warm-up steps of the run itself."""


def read(r):
    return r.setup_s
