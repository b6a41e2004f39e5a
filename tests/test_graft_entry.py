"""The driver's entry points (``__graft_entry__.py``): ``entry()`` hands
back a jittable check step and its arguments, whose answers are the host
oracle's."""

import importlib.util
import os

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _graft():
    spec = importlib.util.spec_from_file_location(
        "__graft_entry__", os.path.join(ROOT, "__graft_entry__.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("jit", [False, True], ids=["eager", "jit"])
def test_entry_returns_a_step_that_answers_as_the_oracle(jit):
    from gochugaru_tpu.engine.oracle import T

    graft = _graft()
    fn, args = graft.entry()
    out = (jax.jit(fn) if jit else fn)(*args)
    _cs, _snap, oracle, checks = graft._world()
    definite = np.asarray(out[0])[: len(checks)]
    want = [oracle.check_relationship(q) == T for q in checks]
    assert definite.tolist() == want
    assert any(want) and not all(want)
