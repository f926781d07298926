"""setup_s (s, host clock): process start to the window's start: imports,
weights made on the device, engine built, every shape warmed up (compiled,
or read from the compile cache)."""


def read(run):
    return run.setup_s
