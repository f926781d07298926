"""A model family for the tests: the deepseek_v3 family, with every function
that `bench/` asks of a family recorded in `CALLED` when it is called
through this module."""

from bench.family import deepseek_v3

INTERFACE = ("check_widths", "program_params", "check_layout", "make_globals",
             "make_layer", "prefill_work", "decode_work", "n_params")
CALLED = set()


def _recorded(name):
    def call(*args, **kwargs):
        CALLED.add(name)
        return getattr(deepseek_v3, name)(*args, **kwargs)
    return call


check_widths = _recorded("check_widths")
program_params = _recorded("program_params")
check_layout = _recorded("check_layout")
make_globals = _recorded("make_globals")
make_layer = _recorded("make_layer")
prefill_work = _recorded("prefill_work")
decode_work = _recorded("decode_work")
n_params = _recorded("n_params")
