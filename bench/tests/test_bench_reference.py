"""The reference draws the same initial state as the program's
``init_state`` from the same seed, without taking it from the program, and
its matrix rule compresses the leaves the program does.  The same rule
compiled into another program may round the last bit of a normal draw
differently, so values agree to float32 rounding, not bit for bit."""

import pytest

import benchtiny  # noqa: F401
from benchtiny import small

RTOL = 4 * 2.0**-23     # a few float32 ulps


@pytest.mark.parametrize("name", ("qwen3-4b.s4096b1.powersgd.1chip",
                                  "olmoe-1b-7b.s4096b2.powersgd.1chip"))
def test_reference_initial_state_is_the_programs(name):
    import jax
    import numpy as np

    import program
    import reference

    cell = small(name)
    key = program.base_key(2**32 + 5)
    prog = program.Program(cell, jax.devices()[:1])
    params, ef = prog.init(key)
    r_params, r_factors = reference.init_state(
        key, reference.Arch.from_config(cell.config), cell.traffic["rank"])

    flat = lambda t: {reference.path_name(p): x for p, x in
                      jax.tree_util.tree_flatten_with_path(t)[0]}
    got, want = flat(params), flat(r_params)
    assert sorted(got) == sorted(want)
    for k in want:
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(want[k]),
                                   rtol=RTOL, atol=0, err_msg=k)
    got_q, want_q = flat(ef.comp), flat(r_factors)
    assert sorted(got_q) == sorted(want_q)
    for k in want_q:
        np.testing.assert_allclose(np.asarray(got_q[k]),
                                   np.asarray(want_q[k]), rtol=RTOL, atol=0,
                                   err_msg=k)
