"""Whether what the timed path served is correct.

Once the window has closed and the program's state is freed, a sample of
the requests the window finished, drawn from the seed and always holding
the longest of them, is run through the plain float32 reference
(``bench/reference/<name>.py``): one full forward pass over each prompt and
its served tokens, with the adapter store's served copy at each position's
version. The number compared is the widest gap by which a served token's
logit lies below the reference's best at that position
(``widest_logit_gap``). A served token that the program computed right is
at most a rounding error below the best; one computed wrong, or altered
after it was computed, lies below it by the spread of the logits.

The limit per cell is in ``bench/limits/<cell>.json``; ``PERF.md`` gives
the readings each was set from.
"""
from __future__ import annotations

import dataclasses
import importlib
import json
import time

import jax.numpy as jnp
import numpy as np

from bench.lib import spec, weights

# served tokens the sample aims at, and the most requests it takes
SAMPLE_TOKENS = 512
SAMPLE_MAX = 8


@dataclasses.dataclass
class Verdict:
    ok: bool
    numbers: dict  # name -> {"value": ..., "limit": ...}


def limits(cell: str) -> dict:
    return json.loads((spec.BENCH / "limits" / f"{cell}.json").read_text())


def sample(finished: list, seed: int) -> list:
    """The longest finished request (prompt + served), then others in an
    order drawn from the seed, until SAMPLE_TOKENS served tokens."""
    if not finished:
        return []
    by_len = sorted(finished, key=lambda o: (len(o.req.tokens)
                                             + len(o.req.out), o.req.uid))
    picked = [by_len[-1]]
    rest = sorted(by_len[:-1], key=lambda o: o.req.uid)
    order = np.random.default_rng(seed).permutation(len(rest))
    total = len(picked[0].req.out)
    for j in order:
        if total >= SAMPLE_TOKENS or len(picked) >= SAMPLE_MAX:
            break
        picked.append(rest[j])
        total += len(rest[j].req.out)
    return picked


def positions_versions(o, n: int) -> np.ndarray:
    """The store version each of the first n positions was computed under:
    prompt positions by the step that prefilled them, the position that
    fed served token i (i >= 1) by the step that emitted it."""
    ver = np.zeros(n, np.int64)
    for a, b, v in o.segments:
        ver[a:b] = v
    n_p = len(o.req.tokens)
    for i in range(1, len(o.req.out)):
        ver[n_p - 1 + i] = o.tokens[i][2]
    return ver


def gaps(cfg: dict, seed: int, picked: list, w: dict, control: bool):
    """Per sampled request: the gaps of the served tokens, and (control)
    of the tokens the lower precision puts first."""
    ref = importlib.import_module(f"bench.reference.{cfg['reference']}")
    s = cfg["deployment"]["max_seq"]
    ad_cfg = cfg.get("adapters")
    raw = mu = None
    if ad_cfg:
        raw = weights.adapter_raw(cfg, seed)
        adj = ref.band_adjacency(ad_cfg["num_tasks"],
                                 ad_cfg["graph"]["bandwidth"])
        mu = ref.bsr_mixing(adj, ad_cfg["eta"], ad_cfg["tau"],
                            ad_cfg["alpha"])
    served, lower = [], []
    for o in picked:
        prompt = np.asarray(o.req.tokens, np.int32)
        out = np.asarray(o.req.out, np.int32)
        n_p, n_o = len(prompt), len(out)
        seq = np.concatenate([prompt, out[:-1]])
        tokens = np.zeros(s, np.int32)
        tokens[: len(seq)] = seq
        rows = n_p - 1 + np.arange(n_o)
        targets = np.zeros(s, np.int32)
        targets[rows] = out
        ver = positions_versions(o, len(seq))
        vidx = np.zeros(s, np.int32)
        ad = None
        if ad_cfg:
            distinct = sorted(set(ver.tolist()))
            vidx[: len(seq)] = np.searchsorted(distinct, ver)
            ad = ref.adapter_versions(mu, raw, o.req.task_id, distinct)
        best, got, ctrl = ref.row_stats(
            w, jnp.asarray(tokens), jnp.asarray(vidx), ad,
            jnp.asarray(targets), w["task_head_bias"][o.req.task_id],
            cfg=cfg, control=control,
        )
        best, got, ctrl = (np.asarray(x, np.float64)[rows]
                           for x in (best, got, ctrl))
        served.append(best - got)
        lower.append(best - ctrl)
    return served, lower


def run(cfg: dict, limits: dict, seed: int, finished: list, w: dict, say,
        control: bool = False) -> Verdict:
    lim = limits["widest_logit_gap"]
    picked = sample(finished, seed)
    if not picked:
        say("check: no finished request to compare")
        return Verdict(False, {"widest_logit_gap": {"value": None,
                                                    "limit": lim}})
    t = time.perf_counter()
    served, lower = gaps(cfg, seed, picked, w, control)
    widest = float(max(g.max() for g in served))
    n_tok = sum(len(g) for g in served)
    say(f"reference: {len(picked)} requests, {n_tok} served tokens, "
        f"{time.perf_counter() - t:.3f} s; widest gap {widest!r}")
    numbers = {"widest_logit_gap": {"value": widest, "limit": lim}}
    if control:
        # the control stands in the program's place: it is judged, and
        # the program's own gap is printed beside it
        ctrl = float(max(g.max() for g in lower))
        numbers["control_widest_gap"] = {"value": ctrl, "limit": lim}
        return Verdict(ctrl <= lim, numbers)
    return Verdict(widest <= lim, numbers)
