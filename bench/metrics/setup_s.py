"""setup_s (s): process start to the window's open, on the host clock:
imports, weights drawn on the device, the warm-up that compiles (or
loads from the persistent cache) every program, and the pre-roll."""


def read(run):
    return run.setup_s
