"""The reference's model is the configuration's architecture module, found by
name (``bench/archs/<arch>.py``).  It draws the same initial state as the
program's ``init_state`` from the same seed, without taking it from the
program, and its matrix rule compresses the leaves the program does.  The
same rule compiled into another program may round the last bit of a normal
draw differently, so values agree to float32 rounding, not bit for bit.

``reference_readings.json`` holds the reference's readings of both cells at
the ``benchtiny`` size, recorded before the model moved out of
``bench/reference.py``; the reference has to reproduce them exactly."""

import json
import textwrap

import pytest

import benchtiny  # noqa: F401
from benchtiny import BENCH, small

import cell as cell_lib

RTOL = 4 * 2.0**-23     # a few float32 ulps
CELLS = ("qwen3-4b.s4096b1.powersgd.1chip",
         "olmoe-1b-7b.s4096b2.powersgd.1chip")
RECORDED = BENCH / "tests" / "reference_readings.json"


@pytest.mark.parametrize("name", CELLS)
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
        key, reference.Model.of(cell.arch, cell.config),
        cell.traffic["rank"])

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


@pytest.mark.parametrize("name", CELLS)
def test_reference_readings_are_the_recorded_ones(name):
    import jax

    import program
    import reference

    recorded = json.loads(RECORDED.read_text())
    seed = recorded["seed"]
    cell = small(name)
    steps = cell.traffic["check_steps"]
    batches = program.batches_for_check(cell, program.make_ring(cell, seed),
                                        steps)
    ref = reference.train(reference.Model.of(cell.arch, cell.config),
                          reference.Optim.from_traffic(cell.traffic),
                          program.base_key(seed), batches, jax.devices()[:1])
    assert {"losses": ref.losses, "grad_norms": ref.grad_norms,
            "change_norms": ref.change_norms} == recorded[name]


@pytest.mark.parametrize("name, flops", zip(CELLS, (3_116_679_168,
                                                   531_050_496)))
def test_cell_flops_per_token(name, flops):
    assert cell_lib.load(name).flops_per_token == flops


TOY = '''
"""A throwaway architecture: embedding, one mixing matrix, head."""

import dataclasses

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Arch:
    vocab: int
    width: int


def from_config(c):
    return Arch(c["vocab_size"], c["toy_width"])


def init_params(key, a):
    ke, kh = jax.random.split(key)
    return {"embed": jax.random.normal(ke, (a.vocab, a.width)) * 0.02,
            "head": jax.random.normal(kh, (a.width, a.vocab)),
            "mix": jnp.eye(a.width)}


def loss(params, tokens, labels, a, mask=None):
    logits = params["embed"][tokens] @ params["mix"] @ params["head"]
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    mask = jnp.ones(labels.shape) if mask is None else mask
    lm = jnp.sum((lse - picked) * mask) / jnp.sum(mask)
    return lm, lm


def matrix_dims(name, shape):
    return 0 if name == "['mix']" else None


def flops_per_token(c, seq):
    return 1000 * c["toy_width"] + seq


PROGRAM_FIELDS = {"toy_width": "d_model"}
'''


def test_an_architecture_is_new_files_found_by_name(tmp_path, monkeypatch):
    import jax
    import numpy as np

    import program
    import reference

    (tmp_path / "archs").mkdir()
    (tmp_path / "archs" / "toy.py").write_text(textwrap.dedent(TOY))
    monkeypatch.setattr(cell_lib, "SEARCH", (tmp_path,) + cell_lib.SEARCH)
    config = {"name": "toy", "arch": "toy", "registry": "qwen3-4b",
              "changed": {"d_model": 16, "vocab_size": 64},
              "vocab_size": 64, "toy_width": 16, "dtype": "float32"}
    traffic = dict(small(CELLS[0]).traffic, seq=32)
    cell = cell_lib.Cell(name="toy.cell", chips=1, config=config,
                         traffic=traffic, limits={})
    assert cell.arch.__file__ == str(tmp_path / "archs" / "toy.py")

    # its FLOP count is the cell's
    assert cell.flops_per_token == 1000 * 16 + 32
    # its program fields are checked along with every config's own
    assert cell_lib.program_config(config).d_model == 16
    with pytest.raises(SystemExit, match="toy_width"):
        cell_lib.program_config(dict(config, toy_width=8))
    # its matrix rule decides which leaves the reference compresses
    model = reference.Model.of(cell.arch, config)
    key = program.base_key(11)
    _, factors = reference.init_state(key, model, 2)
    assert factors["embed"] is None and factors["head"] is None
    assert factors["mix"].shape == (16, 2)
    ring = program.make_ring(cell, 11)
    ref = reference.train(model, reference.Optim.from_traffic(traffic), key,
                          program.batches_for_check(cell, ring, 3),
                          jax.devices()[:1])
    assert len(ref.losses) == 3 and np.isfinite(ref.losses).all()
    assert sorted(ref.grad_norms) == ["['embed']", "['head']", "['mix']"]

    # an explicit "arch": "decoder" is the default
    qwen = cell_lib.load(CELLS[0]).config
    assert "arch" not in qwen
    assert cell_lib.arch_module(dict(qwen, arch="decoder")) is \
        cell_lib.arch_module(qwen)
    assert reference.Model.of(cell_lib.arch_module(dict(qwen, arch="decoder")),
                              qwen) == reference.Model.of(
        cell_lib.arch_module(qwen), qwen)
