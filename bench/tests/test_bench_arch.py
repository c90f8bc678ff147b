"""An architecture enters the benchmark by a module of its own,
``bench/arch/<model_type>.py``, found by name: OLMo's draws the same weights
as before it moved there, a module registered under a new model_type is the
one the program's build, the weights, the cost counts and the check use, and
a model_type without a module fails with the file it looked for."""
import hashlib
import sys
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.arch import olmo
from bench.lib import harness, program, spec, weights
from bench.lib.peaks import PEAKS
from bench.reference import olmo as olmo_ref
from bench.tests import tiny

# sha256 of the tiny configuration's weights and adapter factors, taken
# before OLMo's draws moved into bench/arch/olmo.py
DIGESTS = {
    ("model", 2**33 + 5, 8, "bfloat16"):
        "3239081921f08a37a126c92b7fed6a4370c97e9be8cf4633d8ba3fa7aa08ceca",
    ("raw", 2**33 + 5, None, "bfloat16"):
        "ab899e2ceaa61555da778827332b9f15bea47f33b826a39adf55f3e9fdce7b11",
    ("model", 3, 16, "float32"):
        "f6a04a33ce7ddb85c59e342fb16df6bd0629368debc5cad73a42134cda959631",
}


def _digest(tree: dict) -> str:
    h = hashlib.sha256()
    for k in sorted(tree):
        a = np.asarray(tree[k])
        h.update(k.encode())
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("kind,seed,tasks,dtype", list(DIGESTS))
def test_olmo_weights_are_bitwise_as_before(kind, seed, tasks, dtype):
    cfg = tiny.config(adapters=True)
    dt = getattr(jnp, dtype)
    tree = (weights.model_weights(cfg, seed, tasks, dt) if kind == "model"
            else weights.adapter_raw(cfg, seed, dt))
    assert _digest(tree) == DIGESTS[(kind, seed, tasks, dtype)]


def _stub(calls: list):
    """OLMo's module under another name, counting what is asked of it."""
    mod = types.ModuleType("bench.arch.stub_arch")
    for name in ("arch", "weights", "params", "adapter_raw", "store_raw",
                 "shape"):
        def fn(*a, _name=name, **k):
            calls.append(_name)
            return getattr(olmo, _name)(*a, **k)
        setattr(mod, name, fn)
    return mod


def test_registered_architecture_is_the_one_used(monkeypatch):
    calls, seen = [], []
    monkeypatch.setitem(sys.modules, "bench.arch.stub_arch", _stub(calls))
    row_stats = olmo_ref.row_stats

    def spy(*a, cfg, **k):
        seen.append(cfg["model_type"])
        return row_stats(*a, cfg=cfg, **k)

    monkeypatch.setattr(olmo_ref, "row_stats", spy)
    cfg = tiny.config(adapters=True)
    cfg["model_type"] = "stub_arch"
    assert spec.architecture("stub_arch") is sys.modules[
        "bench.arch.stub_arch"]
    cell = {"name": tiny.CELL, "config": "tiny", "traffic": "tiny",
            "chips": 1}
    r = harness.run(tiny.bench(), cell, cfg, tiny.OPEN, 2**33 + 9, 1.0,
                    False, time.perf_counter(), jax.devices(),
                    PEAKS["TPU v5 lite"], {"widest_logit_gap": 0.1},
                    say=lambda msg: None)
    assert r["correct"], r["checks"]
    # the program's config and tree, the store's layout, the weights (the
    # harness's and the check's adapter factors), the cost counts' shape
    assert {"arch", "params", "store_raw", "weights", "shape"} <= set(calls)
    assert calls.count("adapter_raw") == 2
    assert seen and set(seen) == {"stub_arch"}


def test_missing_architecture_names_its_file():
    cfg = dict(tiny.config(adapters=False), model_type="no_such_arch")
    for call in (lambda: spec.architecture("no_such_arch"),
                 lambda: program.num_tasks(cfg),
                 lambda: weights.model_weights(cfg, 1, 1)):
        with pytest.raises(FileNotFoundError,
                           match=r"bench/arch/no_such_arch\.py"):
            call()
    with pytest.raises(ValueError, match="not a module name"):
        spec.architecture("olmo.x")
