"""What decides `correct`: served tokens against the float32 reference.

Once the window has closed and the program's state is freed, a sample of
the finished requests, drawn from the seed and always holding the longest,
is run through the configuration's plain reference over prompt + served
tokens.  At each served token's position the gap is the reference's best
logit minus the reference's logit of the token the engine served.  The
worst gap over the sample is held to the configuration's `max_gap` limit,
and every request of the window must have served all the tokens it asked
for.  A greedy token from a wrong cache row, a wrong rotary position or a
wrong length mask lies far below the best; rounding in bf16 does not.
"""

from __future__ import annotations

import numpy as np

from bench.family import root_key


def sample(requests: list, k: int, seed: int) -> list:
    """`k` finished requests: the longest, then others drawn from the seed."""
    done = sorted(requests, key=lambda r: (-(r.plen + len(r.output)), r.rid))
    if len(done) <= k:
        return done
    rng = np.random.default_rng([int(seed), 0xC4EC])
    rest = rng.choice(len(done) - 1, size=k - 1, replace=False) + 1
    return [done[0]] + [done[i] for i in sorted(rest)]


def _pad_to(n: int, m: int) -> int:
    return -(-n // m) * m


def batch(reqs: list) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """tokens [B, S]: prompt + served tokens but the last; idx [B, n]: the
    positions whose logits chose the served tokens; served [B, n]; valid
    [B, n].  S is padded to a multiple of 128, so few shapes compile."""
    n = max(len(r.output) for r in reqs)
    s = _pad_to(max(r.plen + len(r.output) - 1 for r in reqs), 128)
    tokens = np.zeros((len(reqs), s), np.int32)
    idx = np.zeros((len(reqs), n), np.int32)
    served = np.zeros((len(reqs), n), np.int32)
    valid = np.zeros((len(reqs), n), bool)
    for b, r in enumerate(reqs):
        seq = list(r.prompt) + list(r.output[:-1])
        tokens[b, :len(seq)] = seq
        m = len(r.output)
        idx[b, :m] = np.arange(r.plen - 1, r.plen - 1 + m)
        idx[b, m:] = r.plen - 1
        served[b, :m] = r.output
        valid[b, :m] = True
    return tokens, idx, served, valid


def gaps(logits, served: np.ndarray) -> np.ndarray:
    """Reference best minus the reference's logit of each served token."""
    logits = np.asarray(logits, np.float32)
    got = np.take_along_axis(logits, served[..., None], axis=-1)[..., 0]
    return logits.max(-1) - got


def check_window(requests: list, config: dict, traffic: dict, seed: int,
                 reference, family, log=print) -> dict:
    """The verdict on a window's requests: `reference` is the configuration's
    plain reference, which makes the seed's weights with `family`'s makers."""
    failed = sum(1 for r in requests if len(r.output) != r.max_new)
    reqs = sample([r for r in requests if len(r.output) == r.max_new],
                  int(traffic["check_requests"]), seed)
    limit = float(config["check"]["max_gap"])
    worst = None
    if reqs:
        tokens, idx, served, valid = batch(reqs)
        logits = reference.logits_at(family, config["dims"], root_key(seed),
                                     tokens, idx)
        g = gaps(logits, served)
        worst = float(g[valid].max())
        log(f"check: {len(reqs)} requests, {int(valid.sum())} served tokens "
            f"compared with the float32 reference")
    checks = {
        "max_gap": {"value": worst, "limit": limit},
        "failed_requests": {"value": failed, "limit": 0},
    }
    return {"correct": worst is not None and failed == 0 and worst <= limit,
            "failed": failed, "checks": checks}
