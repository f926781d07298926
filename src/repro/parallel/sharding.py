"""Sharding policies: logical axis rules → NamedSharding over the production mesh.

Mesh axes: ``pod`` (DCN outer data axis), ``data`` (in-pod DP + FSDP/ZeRO),
``model`` (TP / EP / SP).  Models call ``shard(x, logical_name)`` at
strategic points; the call is a no-op unless a `ShardingPolicy` is active, so
model code stays mesh-agnostic (smoke tests run it on one CPU device).

Weights are 2-D sharded (FSDP over `data` x TP/EP over `model`) so that
ZeRO-1 optimizer states fit at 110B scale; GSPMD inserts the FSDP
all-gathers at use sites (which the overlap pass then schedules — see
`parallel/overlap.py`).  KV caches shard heads over `model` when the arch
has >= tp kv-heads, otherwise the *sequence* dimension (sequence parallelism
— required for decode_32k on kv=2 archs and for long_500k).
"""

from __future__ import annotations

import contextlib
import dataclasses
import re
from typing import Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


DP = ("pod", "data")  # combined data axes (pod may be absent on 2D meshes)


def _dp(mesh: Mesh):
    """Data axes present in this mesh (pod axis optional)."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names) or None


@dataclasses.dataclass
class ShardingPolicy:
    mesh: Mesh
    # sequence-parallel activations: shard seq dim over `model` (long-context)
    seq_parallel: bool = False
    # shard KV-cache sequence (vs heads) over `model`
    kv_seq_shard: bool = False
    # disable FSDP weight sharding (pure TP; for small models)
    fsdp: bool = True

    # ------------------------------------------------------- activations
    def act_spec(self, name: str) -> P:
        dp = _dp(self.mesh)
        sp = "model" if self.seq_parallel else None
        table = {
            "act_btd": P(dp, sp, None),              # [B, S, D]
            "act_btf": P(dp, sp, "model"),           # [B, S, F] ffn hidden
            "act_bthd": P(dp, None, "model", None),  # [B, S, H, hd] heads
            "act_bhsd": P(dp, "model", None, None),  # [B, H, S, hd]
            "logits": P(dp, sp, "model"),            # [B, S, V] vocab-parallel
            "tokens": P(dp, None),                   # [B, S]
            "token": P(dp),                          # [B]
            "act_bd": P(dp, None),                   # [B, D]
            "experts_ecd": P(None, "model", None, None),  # dispatched [E?..]
        }
        if name not in table:
            raise KeyError(f"unknown logical activation {name!r}")
        return table[name]

    def kv_cache_spec(self, n_kv_heads: int) -> P:
        """[B, Hkv, hd, S] cache layout (`models.transformer.init_cache`)."""
        dp = _dp(self.mesh)
        tp = self.mesh.shape.get("model", 1)
        if self.kv_seq_shard or n_kv_heads < tp:
            return P(dp, None, None, "model")  # sequence parallelism
        return P(dp, "model", None, None)      # head parallelism

    def ssm_state_spec(self) -> P:
        """[B, d_inner, N] SSM state: channels over model."""
        return P(_dp(self.mesh), "model", None)

    # ----------------------------------------------------------- weights
    _WEIGHT_RULES: tuple = (
        # (regex on param path, spec builder name)
        (r"embed$",            lambda fs: P("model", fs)),         # [V, D]
        (r"lm_head$",          lambda fs: P(fs, "model")),         # [D, V]
        (r"pos_embed$",        lambda fs: P(None, None)),          # [S, D]
        (r"(wq|wk|wv)$",       lambda fs: P(fs, "model", None)),   # [D, H, hd]
        (r"(bq|bk|bv)$",       lambda fs: P("model", None)),       # [H, hd]
        (r"wo$",               lambda fs: P("model", None, fs)),   # [H, hd, D]
        (r"(w_gate|w_in)$",    lambda fs: P(fs, "model")),         # [D, F]
        (r"w_out$",            lambda fs: P("model", fs)),         # [F, D]
        (r"router$",           lambda fs: P(fs, None)),            # [D, E]
        (r"experts/(w_gate|w_in)$", lambda fs: P("model", fs, None)),  # [E, D, F]
        (r"experts/w_out$",    lambda fs: P("model", None, fs)),   # [E, F, D]
        (r"in_proj$",          lambda fs: P(fs, "model")),         # mamba [D, 2di]
        (r"conv_w$",           lambda fs: P(None, "model")),       # [W, di]
        (r"(x_proj|dt_proj)$", lambda fs: P("model", fs)),         # [di, ...]
        (r"out_proj$",         lambda fs: P("model", fs)),         # [di, D]
        (r"(A_log|conv_b|dt_bias|D_skip)$", lambda fs: P("model",)),  # [di,...]
        (r"(up_proj)$",        lambda fs: P(fs, "model")),         # xlstm [D, 2di]
        (r"(wq_blk|wk_blk|wv_blk)$", lambda fs: P("model", None, None)),  # [nh, d, d]
        (r"down_proj$",        lambda fs: P("model", fs)),         # [di, D]
        (r"(w_i|w_f|w_o|w_z)$", lambda fs: P(fs, "model")),        # slstm in [D, D]
        (r"(r_i|r_f|r_o|r_z)$", lambda fs: P("model", None, None)),  # slstm rec blockdiag
        (r"(norm|scale|bias|gate_scale|gate_bias|b_i|b_f|b_o|b_z|ln)", lambda fs: P()),
    )

    def param_spec(self, path: str, ndim: int) -> P:
        fs = "data" if self.fsdp else None
        for pat, builder in self._WEIGHT_RULES:
            if re.search(pat, path):
                spec = builder(fs)
                # pad spec to tensor rank (stacked-layer leading dims -> None)
                pads = (None,) * (ndim - len(spec))
                return P(*pads, *spec)
        return P()  # replicate by default (norms, small vectors)

    def tree_specs(self, tree) -> object:
        """PartitionSpec pytree matching `tree` (params or their SDS).

        Specs are divisibility-fitted to each leaf's actual shape.
        """
        flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
        specs = []
        for path, leaf in flat:
            pstr = "/".join(_key_str(k) for k in path)
            spec = self.param_spec(pstr, len(leaf.shape))
            specs.append(fit_spec(spec, leaf.shape, self.mesh))
        return jax.tree_util.tree_unflatten(treedef, specs)
        # NOTE: a head_dim-sharding fallback for non-divisible head counts
        # (smollm: 15 heads on 16-way TP) was tried and REFUTED — it removes
        # the replicated q/o FLOPs (compute 1.16 s -> 0.20 s) but the
        # contraction over a sharded head_dim inserts per-layer activation
        # psums (collective 0.43 s -> 52 s).  Replication wins at this scale;
        # see EXPERIMENTS.md §Perf.

    def tree_shardings(self, tree) -> object:
        return jax.tree.map(
            lambda s: NamedSharding(self.mesh, s), self.tree_specs(tree),
            is_leaf=lambda s: isinstance(s, P),
        )


def fit_spec(spec: P, shape: tuple[int, ...], mesh: Mesh) -> P:
    """Drop mesh axes that do not divide the corresponding dim evenly.

    jit input shardings must tile exactly; configs like 5 KV heads over a
    16-way `model` axis or batch=1 over `data` fall back to replication on
    that dim (GSPMD still re-shards intermediates as it sees fit).  Tuple
    entries are trimmed from the right so e.g. ('pod','data') on batch=16
    keeps 'pod' alone when 32 doesn't divide.
    """
    entries = list(spec) + [None] * (len(shape) - len(spec))
    out = []
    for dim, entry in zip(shape, entries):
        if entry is None:
            out.append(None)
            continue
        axes = list(entry) if isinstance(entry, tuple) else [entry]
        while axes:
            prod = 1
            for a in axes:
                prod *= mesh.shape.get(a, 1)
            if prod and dim % prod == 0:
                break
            axes.pop()  # trim from the right
        if not axes:
            out.append(None)
        elif len(axes) == 1:
            out.append(axes[0])
        else:
            out.append(tuple(axes))
    return P(*out)


def _key_str(k) -> str:
    if hasattr(k, "key"):
        return str(k.key)
    if hasattr(k, "idx"):
        return str(k.idx)
    return str(k)


# ------------------------------------------------------- ambient policy API
_ACTIVE: list[ShardingPolicy] = []


@contextlib.contextmanager
def use_policy(policy: Optional[ShardingPolicy]):
    if policy is None:
        yield
        return
    _ACTIVE.append(policy)
    try:
        yield
    finally:
        _ACTIVE.pop()


def current_policy() -> Optional[ShardingPolicy]:
    return _ACTIVE[-1] if _ACTIVE else None


def shard(x, logical_name: str):
    """Constrain activation sharding if a policy is active; else no-op."""
    pol = current_policy()
    if pol is None:
        return x
    spec = pol.act_spec(logical_name)
    return jax.lax.with_sharding_constraint(x, NamedSharding(pol.mesh, spec))


def shard_spec(x, spec: P):
    pol = current_policy()
    if pol is None:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(pol.mesh, spec))
