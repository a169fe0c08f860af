"""The comparison that decides ``correct``, driven through a whole run at a
CPU size: the sound program passes, the lower-precision control fails,
and so does each fault the served cells can have, planted in the timed
path underneath the harness (a token altered where the fused decode block
produces it, a decode step that hands back its state unchanged, an image
score altered where the scorer produces it).

One set-up serves every case: the program is built once at rehearsal size
and driven once per case with the same requests.
"""
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402

SEED = 2 ** 33 + 12345
SECONDS = 3.0


@pytest.fixture(scope="module")
def served():
    import jax

    cell = harness.load_cell("pair.vqa-poisson")
    harness.shrink(cell)
    items, images = harness.schedule(cell, SEED, SECONDS)
    st = harness.set_up(cell, SEED, items, images)
    assert jax.devices()[0].platform == "cpu"
    return cell, st, items, images


def _run(served, control=None):
    cell, st, items, images = served
    out = harness.drive(cell, st, items, images, SECONDS, False)
    readings = harness.served_check(cell, out, SEED, control=control)
    ok, checks = harness.decide(readings, cell.conf["limits"])
    return ok, checks, out


def _wrap_fused(st, alter):
    """Put ``alter(block, cache_in, result)`` behind every engine's fused
    decode block; returns an undo."""
    saved = {}
    for tier, eng in st.engines.items():
        saved[tier] = eng._fused

        def fused(*a, _f=eng._fused, _eng=eng):
            return alter(_eng, _f, a)

        eng._fused = fused

    def undo():
        for tier, eng in st.engines.items():
            eng._fused = saved[tier]
    return undo


def test_sound_run_is_correct(served):
    ok, checks, out = _run(served)
    assert ok, checks
    assert "logit_gap" in checks
    assert "image_score_gap" in checks
    assert checks["failed_requests"] == {"value": 0.0, "limit": 0.0}
    assert sum(r.done for r in out.rows if r.in_window) > 0


def test_failed_request_is_not_correct(served):
    cell = served[0]
    _, _, out = _run(served)
    row = next(r for r in out.rows if r.in_window and r.done)
    row.done, row.failed = False, True  # ended with a failed outcome
    ok, checks = harness.decide(harness.served_check(cell, out, SEED),
                                cell.conf["limits"])
    assert not ok, checks
    assert checks["failed_requests"]["value"] == 1.0


def test_lower_precision_control_is_not_correct(served):
    ok, checks, _ = _run(served, control="fp8")
    assert not ok, checks


def test_token_altered_where_produced_is_not_correct(served):
    def alter(eng, f, a):
        block, cache, keys = f(*a)
        return (block + 1) % eng.cfg.vocab_size, cache, keys

    undo = _wrap_fused(served[1], alter)
    try:
        ok, checks, _ = _run(served)
    finally:
        undo()
    assert not ok, checks


def test_step_returning_its_state_unchanged_is_not_correct(served):
    import jax
    import jax.numpy as jnp

    def alter(eng, f, a):
        before = jax.tree.map(jnp.copy, a[1])
        block, _, keys = f(*a)
        return block, before, keys

    undo = _wrap_fused(served[1], alter)
    try:
        ok, checks, _ = _run(served)
    finally:
        undo()
    assert not ok, checks


def test_image_score_altered_where_produced_is_not_correct(served, monkeypatch):
    from repro.core import complexity

    real = complexity.image_complexity

    def altered(*a, **kw):
        out = dict(real(*a, **kw))
        out["c_img"] = out["c_img"] + np.float32(0.01)
        return out

    monkeypatch.setattr(complexity, "image_complexity", altered)
    ok, checks, _ = _run(served)
    assert not ok, checks
    assert checks["image_score_gap"]["value"] > \
        checks["image_score_gap"]["limit"]
