"""benchmarks/reference.py and GPT-2's adapter (benchmarks/models/gpt2.py)
against the program at the tiny size on the CPU — loss, gradients, the
optimizer step, prefill + decode logits through the paged cache — and a
lower precision failing the same comparisons."""

import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmarks import cells, loops, reference
from benchmarks.models import Dims, gpt2 as adapter
from benchmarks.run import ROOT
from determined_tpu.models import gpt2
from determined_tpu.serve import model as smodel

DIMS = Dims(vocab_size=512, n_positions=128, d_model=64, n_layer=2,
            n_head=4, d_ff=256)
LOSS_AND_GRADS = reference.at_highest(adapter.loss_and_grads,
                                      "dims", "quant", "rows")
LOGITS = reference.at_highest(adapter.logits, "dims", "quant")
CFG = gpt2.Config(vocab_size=512, n_positions=128, d_model=64, n_layer=2,
                  n_head=4, dtype=jnp.float32, attention_impl="reference",
                  remat=False)
OPT = {"learning_rate": 3e-4, "warmup_steps": 100, "decay_steps": 10000,
       "weight_decay": 0.1, "b1": 0.9, "b2": 0.95, "eps": 1e-8,
       "clip_norm": 1.0}
# float32 against float32, summed in another order: 1e-5 of the scale.
TOL = 1e-5


@pytest.fixture(scope="module")
def params():
    return adapter.init_params(jax.random.PRNGKey(3), DIMS)


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(0).integers(0, 512, (4, 33), dtype=np.int32)


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


def test_initial_weights_are_the_trainers(params):
    theirs = gpt2.init(jax.random.PRNGKey(3), CFG)
    assert jax.tree.structure(theirs) == jax.tree.structure(params)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(params)[0],
                            jax.tree.leaves(theirs)):
        assert np.array_equal(np.asarray(a), np.asarray(b)), path
    assert adapter.param_count(DIMS) == gpt2.param_count(CFG) == sum(
        x.size for x in jax.tree.leaves(params))


@pytest.mark.parametrize("quant,holds", [(None, True), ("int8", False),
                                         ("fp8", False)])
def test_loss_and_gradients_against_the_model(params, tokens, quant, holds):
    loss, grads = LOSS_AND_GRADS(params, jnp.asarray(tokens), dims=DIMS,
                                 quant=quant, rows=2)
    want_loss, want = jax.value_and_grad(
        lambda p: gpt2.loss_fn(p, {"tokens": jnp.asarray(tokens)}, CFG))(
            params)
    gaps = [abs(float(loss) - float(want_loss)) / float(want_loss)]
    gaps += [_rel(g, w) for g, w in zip(jax.tree.leaves(grads),
                                        jax.tree.leaves(want))]
    assert (max(gaps) <= TOL * 20) == holds, max(gaps)


def test_adamw_step_is_the_trials_optimizer(params, tokens):
    _, grads = LOSS_AND_GRADS(params, jnp.asarray(tokens), dims=DIMS)
    sched = optax.warmup_cosine_decay_schedule(0.0, 3e-4, 100, 10000)
    tx = optax.chain(optax.clip_by_global_norm(1.0),
                     optax.adamw(sched, b2=0.95, weight_decay=0.1))
    state, theirs = tx.init(params), params
    mine = jax.tree.map(jnp.copy, params)
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    for count in range(3):
        updates, state = tx.update(grads, state, theirs)
        theirs = optax.apply_updates(theirs, updates)
        mine, clipped, mu, nu = reference.adamw_step(
            mine, grads, mu, nu, count, opt=tuple(sorted(OPT.items())))
        if count == 0:   # the schedule starts at 0: step 1 moves nothing
            assert all(np.array_equal(a, b) for a, b in zip(
                jax.tree.leaves(mine), jax.tree.leaves(params)))
    moved = [_rel(np.asarray(a) - np.asarray(p), np.asarray(b) - np.asarray(p))
             for a, b, p in zip(jax.tree.leaves(mine), jax.tree.leaves(theirs),
                                jax.tree.leaves(params))]
    assert max(moved) < 1e-3, moved
    assert float(reference.learning_rate(OPT, 100)) == pytest.approx(3e-4)
    assert float(reference.learning_rate(OPT, 10000)) == pytest.approx(0, abs=1e-9)


def _serve_logits(params, prompt, n_new):
    """Prefill then decode through the program's paged cache (block 8,
    float32, gather reference), feeding the reference's own greedy tokens
    → logits [n_new, V]."""
    block, max_blocks = 8, 8
    cache = smodel.init_paged_cache(CFG, 17, block, dtype=jnp.float32)
    table = np.arange(max_blocks, dtype=np.int32)
    padded = np.zeros((32,), np.int32)
    padded[:len(prompt)] = prompt
    cache, logits = smodel.paged_prefill(
        params, cache, jnp.asarray(padded), jnp.int32(len(prompt)),
        jnp.int32(0), jnp.asarray(table), CFG)
    out, seq = [np.asarray(logits)], list(prompt)
    for _ in range(n_new - 1):
        seq.append(int(np.argmax(out[-1])))
        tables = np.full((2, max_blocks), 16, np.int32)
        tables[0] = table
        cache, logits = smodel.paged_decode_step(
            params, cache, jnp.asarray([seq[-1], 0], jnp.int32),
            jnp.asarray([len(seq) - 1, 0], jnp.int32), jnp.asarray(tables),
            CFG, attention_impl="reference")
        out.append(np.asarray(logits[0]))
    seq.append(int(np.argmax(out[-1])))
    return np.stack(out), seq[len(prompt):]


@pytest.mark.parametrize("quant,holds", [(None, True), ("int8", False)])
def test_prefill_and_decode_logits_through_the_paged_cache(params, quant,
                                                            holds):
    prompt = np.random.default_rng(1).integers(0, 512, 19).astype(np.int32)
    served_logits, served = _serve_logits(params, prompt, 6)
    rows = np.zeros((1, 32), np.int32)
    rows[0, :19 + 6] = np.concatenate([prompt, served])
    gather = (18 + np.arange(6))[None].astype(np.int32)
    ref = np.asarray(LOGITS(params, jnp.asarray(rows), jnp.asarray(gather),
                            dims=DIMS, quant=quant))[0]
    assert (_rel(served_logits, ref) <= TOL * 20) == holds


def test_replay_gaps_read_zero_for_the_references_own_choice(params):
    prompt = np.random.default_rng(2).integers(0, 512, 11).astype(np.int32)
    _, served = _serve_logits(params, prompt, 5)
    gaps = reference.replay_gaps(
        adapter, params, DIMS, [(prompt, np.asarray(served, np.int32))], width=32,
        max_new=8, rows=2)
    assert len(gaps) == 5 and max(gaps) <= 1e-4
    wrong = list(served)
    wrong[2] = (wrong[2] + 1) % 512          # one token altered
    gaps = reference.replay_gaps(
        adapter, params, DIMS, [(prompt, np.asarray(wrong, np.int32))], width=32,
        max_new=8, rows=2)
    assert gaps[2] > 0.05
    control = reference.replay_gaps(
        adapter, params, DIMS, [(prompt, np.asarray(served, np.int32))], width=32,
        max_new=8, rows=2, control="int8")
    assert len(control) == 5 and min(control) >= 0.0


def test_sketch_is_linear_and_keeps_norms(params):
    a = reference.sketch(params, 7)
    doubled = reference.sketch(jax.tree.map(lambda x: 2 * x, params), 7)
    other = reference.sketch(params, 8)
    for leaf, v in a.items():
        assert v.shape == (jax.tree.leaves(
            {leaf: x for leaf2, x in reference._paths(params)
             if leaf2 == leaf})[0].shape[-1],)
        np.testing.assert_allclose(doubled[leaf], 2 * v, rtol=1e-5, atol=1e-6)
    assert not np.allclose(a["wte"], other["wte"])
    norms = reference.leaf_norms(params)
    # the squared norm of a sketch estimates the leaf's, to ~sqrt(2/columns)
    assert np.linalg.norm(a["wte"]) == pytest.approx(norms["wte"], rel=0.5)
    cols = reference.column_norms(params)
    assert np.linalg.norm(cols["blocks/qkv/kernel"]) == pytest.approx(
        norms["blocks/qkv/kernel"], rel=1e-4)


# ------------------------------------------ the move to the adapter (PR 27)

# Recorded on the CPU from commit 1b802bc, the last before GPT-2's reference
# moved out of reference.py into its adapter, by the calls below: seed 0 at
# the tiny size. The move may not change a bit of them.
PARENT = {
    "init": "bb2ec124813d639f58758a09b1ad2c6e"
            "e2693e80f89dadbd9ac682fc56584da1",
    "losses": ["0x1.916c300000000p+2", "0x1.8fb0a80000000p+2",
               "0x1.8edfee0000000p+2"],
    "gaps": ["0x1.e5bf960000000p-1", "0x1.4c9d780000000p-1",
             "0x1.168b1a0000000p-1", "0x1.a875460000000p-1",
             "0x1.754e5c0000000p-1", "0x1.355e5a0000000p+0",
             "0x1.3496f60000000p-1", "0x1.f287fa0000000p-2",
             "0x1.3991ce0000000p-1", "0x1.5a6a280000000p-1",
             "0x1.965fda0000000p-1", "0x1.42bbae0000000p-1"],
    "gaps_int8": [0.0] * 8 + [float.fromhex("0x1.1eb8000000000p-11")]
                 + [0.0] * 3,
}


def _tiny_cell(workload):
    return cells.resolve(ROOT, cells.load_manifest(ROOT), workload, True)


def _fixed_requests():
    rng = np.random.default_rng(27)
    return [{"prompt": rng.integers(0, 512, p).astype(np.int32),
             "tokens": rng.integers(0, 512, n).astype(np.int32).tolist()}
            for p, n in ((5, 2), (9, 3), (7, 2), (12, 4), (6, 1))]


@pytest.mark.parametrize("workload", ["train-medium-1chip",
                                      "serve-large-decode"])
def test_weights_are_the_parents_to_the_last_bit(workload):
    digest = hashlib.sha256()
    for leaf in jax.tree.leaves(loops.serve_params(_tiny_cell(workload), 0)):
        digest.update(np.asarray(leaf).tobytes())
    assert digest.hexdigest() == PARENT["init"]


def test_three_reference_losses_are_the_parents_to_the_last_bit():
    ref = loops.train_standin(_tiny_cell("train-medium-1chip"), 0,
                              jax.devices()[:1])
    assert [x.hex() for x in ref["losses"]] == PARENT["losses"]


@pytest.mark.parametrize("control,want", [
    (None, [float.fromhex(x) for x in PARENT["gaps"]]),
    ("int8", PARENT["gaps_int8"])])
def test_replay_gaps_are_the_parents_to_the_last_bit(control, want):
    gaps = loops.serve_gaps(_tiny_cell("serve-large-decode"), 0,
                            _fixed_requests(), control=control)
    assert gaps == want


@pytest.mark.parametrize("name,parameters", [("gpt2-medium", 354_823_168),
                                             ("gpt2-large", 774_030_080)])
def test_work_counts_every_parameter_of_a_dense_model(name, parameters):
    """What the two mfu readers multiply by is what `param_count` gave
    them at 1b802bc: no reading moves."""
    with open(os.path.join(ROOT, "benchmarks/configs", name + ".json")) as f:
        dims = adapter.dims(json.load(f))
    hash(dims)                                   # a static jit argument
    work = adapter.work(dims)
    assert work["params_per_token"] == parameters == adapter.param_count(dims)
    assert work["q_heads"] == work["kv_heads"] == dims["n_head"]
    assert work["q_heads"] * work["head_dim"] == dims["d_model"]
    assert work["attn_layers"] == dims["n_layer"]
