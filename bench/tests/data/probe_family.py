"""A model family for the tests: the dense family, with every function that
`bench/` asks of a family recorded in `CALLED` when it is called through
this module, and a width check that states no `d_ff`, as a family of expert
layers states none."""

from bench.family import compare_widths, dense

INTERFACE = ("check_widths", "program_params", "check_layout", "make_globals",
             "make_layer", "prefill_work", "decode_work", "n_params")
CALLED = set()


def _recorded(name):
    def call(*args, **kwargs):
        CALLED.add(name)
        return getattr(dense, name)(*args, **kwargs)
    return call


def check_widths(config, arch_cfg):
    CALLED.add("check_widths")
    have = dense.widths(arch_cfg)
    del have["d_ff"]
    compare_widths(config, have)


program_params = _recorded("program_params")
check_layout = _recorded("check_layout")
make_globals = _recorded("make_globals")
make_layer = _recorded("make_layer")
prefill_work = _recorded("prefill_work")
decode_work = _recorded("decode_work")
n_params = _recorded("n_params")
