"""A run whose timed path is broken underneath must come out not correct.

Each test drives a whole run of a cell (set-up, window, check) at a size
the CPU holds, with the look for a chip skipped and one fault planted in
the system under test.  The cell runs on one chip, so no exchange between
chips exists to be left out, and one problem a solve, so no half of a batch.
"""
import jax
import pytest

import tiny

ALL = ["da320.entropic"]


@pytest.fixture(autouse=True)
def fresh_programs():
    jax.clear_caches()           # a planted fault must reach the compiled programs
    yield
    jax.clear_caches()


def _plant(monkeypatch, alter):
    from repro.core import sinkhorn

    original = sinkhorn.sinkhorn_log

    def planted(C, a, b, eps, max_iters, tol):
        return alter(original, C, a, b, eps=eps, max_iters=max_iters, tol=tol)

    monkeypatch.setattr(sinkhorn, "sinkhorn_log", planted)


def _unchanged(solve, C, a, b, **kw):
    """Every iteration returns the potentials it was given."""
    return solve(C, a, b, **dict(kw, max_iters=0))


def _reversed(solve, C, a, b, **kw):
    res = solve(C, a, b, **kw)
    return res._replace(plan=res.plan[:, ::-1])


def _column_emptied(solve, C, a, b, **kw):
    """The plan with its first target column left without mass."""
    res = solve(C, a, b, **kw)
    return res._replace(plan=res.plan.at[:, 0].set(0.0))


def _run(name):
    return tiny.execute(tiny.cell(name), seed=3)


@pytest.mark.parametrize("name", ALL)
def test_unplanted_run_is_correct(name):
    assert _run(name)["correct"]


@pytest.mark.parametrize("name", ALL)
def test_step_that_returns_its_state_unchanged(monkeypatch, name):
    _plant(monkeypatch, _unchanged)
    assert not _run(name)["correct"]


@pytest.mark.parametrize("alter", [_reversed, _column_emptied])
@pytest.mark.parametrize("name", ALL)
def test_answer_altered_where_it_is_produced(monkeypatch, name, alter):
    _plant(monkeypatch, alter)
    assert not _run(name)["correct"]
