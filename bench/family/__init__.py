"""Model families, and what every family shares.

A configuration's `family` names `bench/family/<family>.py`, which
`bench.spec.family_module` finds.  That module is all that `bench/` knows
of a model's shapes; nothing outside this directory names a leaf or counts
a family's work.  It exposes:

- `check_widths(config, arch_cfg)`: refuse a configuration whose widths the
  program's own `repro.configs` entry does not share (`compare_widths`
  below, over the keys that family has);
- `program_params(root, dims)`: the seed's bf16 weights in the program's
  pytree layout, made in one call that the harness jits;
- `check_layout(program_shapes, dims)`: refuse a program whose own init
  lays its parameters out otherwise;
- `make_globals(root, dims)` and `make_layer(root, dims, layer)`: the same
  values, a part at a time, for the family's reference;
- `prefill_work(dims, plen)` and `decode_work(dims, positions)`: the
  (FLOPs, bytes) the algorithm needs, from shapes alone;
- `n_params(dims)`.

Shared here, so that a seed, a byte and a roofline mean the same in every
family: `root_key`, the served type `DTYPE` and its `BYTES`, `least_time`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

DTYPE = jnp.bfloat16
BYTES = 2  # bf16 weights, cache rows and logits


def root_key(seed: int) -> jax.Array:
    """A key from any whole number, also one past 32 bits."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    words = np.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF], np.uint32)
    key = jax.random.PRNGKey(0)
    return jax.random.fold_in(jax.random.fold_in(key, words[0]), words[1])


def least_time(flops: float, nbytes: float, peaks: dict) -> float:
    """The roofline: the larger of compute time and memory time at peak."""
    return max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])


def compare_widths(config: dict, have: dict) -> None:
    """Raise unless the configuration's `dims` state every key of `have`,
    the program's values of the widths its family has, with that value."""
    d = config["dims"]
    bad = {k: (d.get(k), v) for k, v in have.items() if d.get(k) != v}
    if bad:
        raise ValueError(f"configuration {config['name']!r} disagrees with "
                         f"repro.configs {config['arch']!r} (stated, program): {bad}")
