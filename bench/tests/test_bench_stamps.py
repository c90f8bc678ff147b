"""The readers of the program's own request stamps: the scheduler's wait
(``sched_wait_p90_ms``) and admission to first token (``prefill_p90_ms``),
on hand-made requests and in a traced run of the harness at a tiny size;
a span that holds the window's close is left out, and named on standard
error; a program without the stamps reports neither."""
import time
import types

import jax
import pytest

from bench.lib import harness, spec
from bench.lib.harness import Obs
from bench.lib.peaks import PEAKS
from bench.tests import tiny

T_OPEN, T_CLOSE = 100.0, 200.0


def _ctx(reqs, dues, first=None, bound=None):
    """Requests due at ``dues``, each with its first token at ``first`` and
    first holding a slot in the step begun at ``bound`` (harness clock;
    default: inside the window, and not seen)."""
    first = first or [T_OPEN + 50] * len(reqs)
    bound = bound or [None] * len(reqs)
    obs = {i: Obs(req=r, due=d, bound=b,
                  tokens=[(t, 0, 0)] if t is not None else [])
           for i, (r, d, t, b) in enumerate(zip(reqs, dues, first, bound))}
    return types.SimpleNamespace(obs=obs, t_open=T_OPEN, t_close=T_CLOSE)


def _req(submit, admit, first, prompt=4):
    return types.SimpleNamespace(submit_time=submit, admit_time=admit,
                                 first_token_time=first, tokens=[1] * prompt)


def test_stamp_readers_on_requests_due_in_the_window():
    # ten requests due in the window: waits 0..9 ms, prefills 10..100 ms;
    # one due before the window, one closed-loop request (no due time) and
    # one whose first token came after the close are left out
    reqs = [_req(5.0, 5.0 + 1e-3 * k, 5.0 + 1e-3 * k + 1e-2 * (k + 1))
            for k in range(10)]
    reqs += [_req(0.0, 9.0, 20.0)] * 3
    dues = [T_OPEN + k for k in range(10)] + [T_OPEN - 1, None, T_OPEN]
    first = [T_OPEN + 50] * 12 + [T_CLOSE + 1]
    ctx = _ctx(reqs, dues, first)
    assert spec.reader("sched_wait_p90_ms")(ctx) == pytest.approx(8.1)
    assert spec.reader("prefill_p90_ms")(ctx) == pytest.approx(91.0)


def test_stamp_readers_leave_out_only_spans_that_hold_the_close(capsys):
    """Admitted in the window with its first token after the close: its
    wait counts, its prefill does not. Admitted after the close: its
    prefill counts, its wait does not."""
    reqs = [_req(1.0, 1.002, 1.5), _req(2.0, 2.004, 2.9, prompt=1210),
            _req(3.0, 3.3, 3.4)]
    dues = [T_OPEN, T_OPEN + 80, T_OPEN + 99]
    first = [T_OPEN + 50, T_CLOSE + 5, T_CLOSE + 9]
    bound = [T_OPEN + 1, T_OPEN + 81, T_CLOSE + 6]
    ctx = _ctx(reqs, dues, first, bound)
    assert spec.reader("sched_wait_p90_ms")(ctx) == pytest.approx(3.8)
    assert ("sched_wait_p90_ms: left out 1 of 3 requests due in the window, "
            "admitted after the close") in capsys.readouterr().err
    assert spec.reader("prefill_p90_ms")(ctx) == pytest.approx(458.2)
    err = capsys.readouterr().err
    assert "prefill_p90_ms: left out 1 of 3 requests" in err
    assert "prompts [1210] tokens" in err


def test_stamp_readers_report_nothing_without_stamps():
    """A program whose requests carry only ``submit_time``, and a request
    admitted whose first token has not come."""
    bare = types.SimpleNamespace(submit_time=1.0)
    ctx = _ctx([bare, bare], [T_OPEN, T_OPEN + 1])
    assert spec.reader("sched_wait_p90_ms")(ctx) is None
    assert spec.reader("prefill_p90_ms")(ctx) is None
    waiting = _req(1.0, 2.0, None)
    ctx = _ctx([waiting], [T_OPEN], [None])
    assert spec.reader("sched_wait_p90_ms")(ctx) is None
    assert spec.reader("prefill_p90_ms")(ctx) is None


def test_traced_open_loop_reports_the_stamp_metrics():
    cell = {"name": tiny.CELL, "config": "tiny", "traffic": "tiny",
            "chips": 1}
    r = harness.run(tiny.bench(), cell, tiny.config(), tiny.OPEN, 2**33 + 11,
                    1.5, True, time.perf_counter(), jax.devices(),
                    PEAKS["TPU v5 lite"], {"widest_logit_gap": 0.1},
                    say=lambda msg: None)
    assert r["correct"], r["checks"]
    m = r["metrics"]
    assert m["sched_wait_p90_ms"]["value"] >= 0
    assert m["prefill_p90_ms"]["value"] > 0
    assert m["sched_wait_p90_ms"]["unit"] == m["prefill_p90_ms"]["unit"] == "ms"
