"""Validate the driver entry points (__graft_entry__.py) on the CPU mesh."""

import os
import sys

import jax
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import __graft_entry__ as graft  # noqa: E402


@pytest.mark.slow  # end-to-end driver dryrun over an 8-device virtual mesh
def test_dryrun_multichip_8():
    graft.dryrun_multichip(8)


@pytest.mark.slow  # end-to-end driver dryrun over an 8-device virtual mesh
def test_dryrun_multichip_2():
    graft.dryrun_multichip(2)


def test_entry_traces():
    """entry()'s fn must be jit-traceable (full compile check runs on TPU)."""
    kept = []

    def arguments():
        # ``entry()`` draws 355M weights with an unjitted ``model.init``:
        # called under ``eval_shape`` it is traced and never run (run, XLA's
        # threads go on computing it for a minute after the case returned)
        fn, args = graft.entry()
        kept.append(fn)
        return args

    args = jax.eval_shape(arguments)
    out = jax.eval_shape(kept[0], *args)
    assert out.shape == ()
