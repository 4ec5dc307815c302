"""The dropless dispatch's moves of rows: a token's k rows fetched and summed
against the one-line definition, forward and every cotangent, and the
benchmark's reading of what those moves cost on recorded trace rows."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models import moe

F32 = jnp.float32


def _definition(rows, dest, gates=None):
    picked = rows[dest].astype(F32)
    if gates is not None:
        picked = picked * gates[..., None]
    return picked.sum(1).astype(rows.dtype)


def _case(t, k, d, experts=4, block=8, idle_expert=None, seed=0):
    """A dispatch plan with its rows, gates and an upstream gradient.
    ``idle_expert`` gets no pair; with ``block`` 8 and counts that are no
    multiples of it, every other expert's group ends in padding rows."""
    rng = np.random.default_rng(seed)
    open_to = [e for e in range(experts) if e != idle_expert]
    gate_idx = np.stack([
        rng.choice(open_to, size=k, replace=False) for _ in range(t)
    ]).astype(np.int32)
    n_pad = moe._row_budget(t * k, block, experts)
    plan = moe._dispatch_plan(jnp.asarray(gate_idx), experts, block, n_pad)
    return {
        "plan": plan,
        "rows": jnp.asarray(rng.standard_normal((n_pad, d)), F32),
        "x": jnp.asarray(rng.standard_normal((t, d)), F32),
        "gates": jnp.asarray(rng.random((t, k)), F32),
        "d_out": jnp.asarray(rng.standard_normal((t, d)), F32),
        "experts": experts,
    }


# (tokens, k, D): k 1 / 2 / 8, D lane-aligned and not, T odd
SHAPES = [(13, 1, 16), (37, 2, 48), (21, 2, 128), (9, 8, 256), (40, 8, 24)]


@pytest.mark.parametrize("weighted", [False, True])
@pytest.mark.parametrize("t,k,d", SHAPES)
def test_k_rows_summed_is_its_definition(t, k, d, weighted):
    case = _case(t, k, d, experts=max(4, k + 1), idle_expert=0)
    gates = case["gates"] if weighted else None
    got = moe._k_rows_summed(case["rows"], case["plan"]["dest"], gates)
    want = _definition(case["rows"], case["plan"]["dest"], gates)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


def test_the_plan_has_padding_rows_and_an_idle_expert():
    case = _case(37, 2, 48, idle_expert=1)
    padded = np.asarray(case["plan"]["padded"])
    pair = np.asarray(case["plan"]["row_pair"])
    assert padded[1] == 0 and (padded[[0, 2, 3]] > 0).all()
    assert (pair == 37 * 2).sum() == pair.size - 37 * 2 > 0


@pytest.mark.parametrize("idle_expert", [None, 2])
@pytest.mark.parametrize("t,k,d", SHAPES)
def test_tokens_of_rows_cotangents(t, k, d, idle_expert):
    """``d_rows`` and ``d_gates`` against the definition's own transpose
    (a scatter-add, which a padding row never enters)."""
    case = _case(t, k, d, experts=max(4, k + 2), idle_expert=idle_expert)
    plan, d_out = case["plan"], case["d_out"]
    out, vjp = jax.vjp(
        lambda r, g: moe._tokens_of_rows(r, g, plan),
        case["rows"], case["gates"],
    )
    want_out, want_vjp = jax.vjp(
        lambda r, g: _definition(r, plan["dest"], g),
        case["rows"], case["gates"],
    )
    np.testing.assert_allclose(out, want_out, rtol=1e-6, atol=1e-6)
    for got, want in zip(vjp(d_out), want_vjp(d_out)):
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # a padding row takes no gradient: its gate is the zero row's
    d_rows = np.asarray(vjp(d_out)[0])
    assert not d_rows[np.asarray(plan["row_pair"]) == t * k].any()


@pytest.mark.parametrize("idle_expert", [None, 2])
@pytest.mark.parametrize("t,k,d", SHAPES)
def test_rows_of_tokens_cotangent(t, k, d, idle_expert):
    """``d_x[t]`` is the plain sum of the token's k row gradients, whatever
    the padding rows hold."""
    case = _case(t, k, d, experts=max(4, k + 2), idle_expert=idle_expert)
    plan = case["plan"]
    rows, vjp = jax.vjp(lambda x: moe._rows_of_tokens(x, plan), case["x"])
    pair = np.asarray(plan["row_pair"])
    real = pair < t * k
    np.testing.assert_array_equal(
        np.asarray(rows)[real], np.asarray(case["x"])[pair[real] // k]
    )
    assert not np.asarray(rows)[~real].any()
    (d_x,) = vjp(case["rows"])
    np.testing.assert_allclose(
        d_x, _definition(case["rows"], plan["dest"]), rtol=1e-6, atol=1e-6
    )


@pytest.mark.parametrize("t,k,d", SHAPES)
def test_d_gates_from_the_row_dots_is_the_parents_einsum(t, k, d):
    case = _case(t, k, d, experts=max(4, k + 1))
    plan, d_out = case["plan"], case["d_out"]
    _, vjp = jax.vjp(
        lambda g: moe._tokens_of_rows(case["rows"], g, plan), case["gates"]
    )
    (d_gates,) = vjp(d_out)
    assert d_gates.dtype == F32
    parents = jnp.einsum(
        "tkd,td->tk", case["rows"][plan["dest"]].astype(F32),
        d_out.astype(F32),
    )
    np.testing.assert_allclose(d_gates, parents, rtol=1e-5, atol=1e-5)


def test_bfloat16_rows_are_summed_in_float32():
    """A token whose rows hold 256, 1, 1, 1, 1, 1, 1, 1: summed in bfloat16
    the ones are lost to 256's spacing of 2 (256 or 262, by the order);
    the float32 sum is 263 and rounds once, to 264."""
    case = _case(16, 8, 128, experts=9)
    dest = case["plan"]["dest"]
    rows = jnp.ones_like(case["rows"], jnp.bfloat16).at[dest[:, 0]].set(256)
    got = moe._k_rows_summed(rows, dest)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(got, np.float32), 264.0)


# -- the kernel, under the TPU interpreter (it models the DMAs) ---------------

# (tokens, k, D, dtype): one row is whole native tiles in each; T is odd,
# below one chunk, and past one block of 512
KERNEL_SHAPES = [
    (13, 1, 1024, F32), (37, 2, 2048, jnp.bfloat16), (9, 8, 1024, F32),
    (530, 2, 1024, F32),
]


@pytest.mark.parametrize("weighted", [False, True])
@pytest.mark.parametrize("t,k,d,dtype", KERNEL_SHAPES)
def test_the_kernel_is_the_definition(t, k, d, dtype, weighted):
    from jax.experimental.pallas import tpu as pltpu

    from dlrover_tpu.ops import row_gather_sum

    assert row_gather_sum.kernel_fits(d, k, dtype)
    case = _case(t, k, d, experts=max(4, k + 1), idle_expert=0)
    rows = case["rows"].astype(dtype)
    gates = case["gates"] if weighted else None
    dest = case["plan"]["dest"]
    want = np.asarray(_definition(rows, dest, gates), np.float32)
    for form in (rows, rows.reshape(rows.shape[0], -1, 128)):
        got = row_gather_sum.gather_sum(
            form, dest, gates, interpret=pltpu.InterpretParams()
        )
        assert got.shape == (t, d) and got.dtype == dtype
        # float32: another order of the k terms; bfloat16: one rounding
        np.testing.assert_allclose(
            np.asarray(got, np.float32), want,
            rtol=1e-6 if dtype == F32 else 2 ** -7, atol=1e-6,
        )


def test_the_kernel_sums_bfloat16_rows_in_float32():
    from jax.experimental.pallas import tpu as pltpu

    from dlrover_tpu.ops import row_gather_sum

    case = _case(16, 8, 2048, experts=9)
    dest = case["plan"]["dest"]
    rows = jnp.ones_like(case["rows"], jnp.bfloat16).at[dest[:, 0]].set(256)
    got = row_gather_sum.gather_sum(
        rows, dest, interpret=pltpu.InterpretParams()
    )
    np.testing.assert_array_equal(np.asarray(got, np.float32), 264.0)


@pytest.mark.parametrize("d,k,dtype,fits", [
    (2048, 8, jnp.bfloat16, True), (1024, 8, F32, True),
    (4096, 2, jnp.bfloat16, True),
    # Granite-4.0-H-Small: one tile of tokens' ten rows is 1.25 MiB, the slot
    (4096, 10, jnp.bfloat16, True),
    (1024, 8, jnp.bfloat16, False),    # half a bfloat16 tile a row
    (1600, 2, jnp.bfloat16, False), (64, 2, F32, False), (48, 2, F32, False),
    (2048, 64, jnp.bfloat16, False),   # a tile of tokens overflows a slot
])
def test_the_kernel_takes_rows_of_whole_tiles_only(d, k, dtype, fits):
    from dlrover_tpu.ops import row_gather_sum

    assert row_gather_sum.kernel_fits(d, k, dtype) is fits
    if not fits:
        with pytest.raises(ValueError, match="kernel_fits"):
            row_gather_sum.gather_sum(
                jnp.zeros((8, d), dtype), jnp.zeros((4, k), jnp.int32)
            )


@pytest.mark.parametrize("d,k,dtype,chunk", [
    # the shapes that fitted a 1 MiB slot keep the chunk they had
    (2048, 8, jnp.bfloat16, 32),       # OLMoE, JoyAI-LLM-Flash
    (4096, 2, jnp.bfloat16, 64),       # Mixtral
    (1024, 8, F32, 32),
    (2048, 1, jnp.bfloat16, 256),
    # one native tile of tokens where ten rows of 4,096 overflow it
    (4096, 10, jnp.bfloat16, 16),
    (4096, 8, jnp.bfloat16, 16),
    (2048, 64, jnp.bfloat16, 0),       # past the VMEM plan
])
def test_the_slot_follows_from_k_d_and_the_dtype(d, k, dtype, chunk):
    from dlrover_tpu.ops import row_gather_sum

    assert row_gather_sum._chunk_tokens(d, k, dtype) == chunk


def test_ten_rows_of_4096_a_token_are_fetched_and_summed():
    """Granite's combine at its own row width and k, a few tokens past one
    chunk of 16: the kernel under the TPU interpreter is the definition."""
    from jax.experimental.pallas import tpu as pltpu

    from dlrover_tpu.ops import row_gather_sum

    case = _case(37, 10, 4096, experts=12, idle_expert=0)
    rows = case["rows"].astype(jnp.bfloat16)
    dest = case["plan"]["dest"]
    want = np.asarray(_definition(rows, dest, case["gates"]), np.float32)
    got = row_gather_sum.gather_sum(
        rows, dest, case["gates"], interpret=pltpu.InterpretParams()
    )
    np.testing.assert_allclose(
        np.asarray(got, np.float32), want, rtol=2 ** -7, atol=1e-6
    )


# -- row-tiled rows through the GEMMs and the layer ---------------------------


@pytest.mark.parametrize("x_tiled,out_tiled", [
    (True, False), (False, True), (True, True),
])
def test_grouped_matmul_takes_and_gives_row_tiled_rows(x_tiled, out_tiled):
    """Same numbers as the plain call, forward, ``dx`` (in the form ``x``
    came in) and ``dw``, with a row-tiled operand on either side."""
    from dlrover_tpu.ops.grouped_matmul import grouped_matmul

    rng = np.random.default_rng(4)
    n, k, m, block = 48, 256, 384, 8
    x = jnp.asarray(rng.standard_normal((n, k)), F32)
    w = jnp.asarray(rng.standard_normal((3, k, m)) * 0.1, F32)
    dy = jnp.asarray(rng.standard_normal((n, m)), F32)
    sizes = jnp.asarray([16, 0, 24], jnp.int32)     # 8 rows of budget left

    def tiled(a, on):
        return a.reshape(a.shape[0], -1, 128) if on else a

    want, want_vjp = jax.vjp(
        lambda x, w: grouped_matmul(x, w, sizes, block), x, w
    )
    got, got_vjp = jax.vjp(
        lambda x, w: grouped_matmul(x, w, sizes, block, out_tiled),
        tiled(x, x_tiled), w,
    )
    assert got.shape == tiled(want, out_tiled).shape
    np.testing.assert_array_equal(got.reshape(want.shape), want)
    dx, dw = got_vjp(tiled(dy, out_tiled))
    want_dx, want_dw = want_vjp(dy)
    assert dx.shape == tiled(x, x_tiled).shape
    np.testing.assert_array_equal(dx.reshape(x.shape), want_dx)
    np.testing.assert_array_equal(dw, want_dw)


def test_a_layer_whose_rows_are_whole_tiles_runs_them_row_tiled(monkeypatch):
    """d_model 1024 in float32 is a whole tile a row: the layer's rows go
    row-tiled through the GEMMs and the kernel.  Same output and gradients
    as the plain form, which the same layer takes when told nothing fits."""
    from dlrover_tpu.ops import row_gather_sum

    x = jnp.asarray(
        np.random.default_rng(5).standard_normal((2, 12, 1024)), F32
    )
    layer = moe.MoEMlp(
        num_experts=4, d_ff=128, top_k=2, activation="swiglu", dtype=F32,
        param_dtype=F32, dispatch="grouped", gmm_block_rows=8,
        norm_topk_prob=False,
    )
    params = layer.init(jax.random.PRNGKey(5), x)
    forms = []
    real = row_gather_sum.gather_sum

    def seen(rows, *args, **kwargs):
        forms.append(rows.shape)
        return real(rows, *args, **kwargs)

    monkeypatch.setattr(row_gather_sum, "gather_sum", seen)

    def loss(p, x):
        out, aux = layer.apply(p, x)
        return jnp.sum(out ** 2) + aux

    tiled = jax.value_and_grad(loss, argnums=(0, 1))(params, x)
    # combine's forward and the transpose of rows-of-tokens
    assert forms == [(80, 8, 128), (80, 8, 128)]
    monkeypatch.setattr(row_gather_sum, "kernel_fits", lambda *a: False)
    plain = jax.value_and_grad(loss, argnums=(0, 1))(params, x)
    assert len(forms) == 2
    for got, want in zip(jax.tree.leaves(tiled), jax.tree.leaves(plain)):
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


# -- the benchmark's reading of these moves, on recorded rows ----------------

GROUPED = "jit(_train_step)/blocks/moe/moe._grouped_forward/"
BACKWARD = "jit(_train_step)/transpose(jvp(blocks))/moe/moe._grouped_forward/"
ROWS = [
    ["while.3", "", 0, 2000],
    ["fusion.7", GROUPED + "sort/cumsum", 0, 100],
    ["fusion.484", BACKWARD + "scatter/gather", 100, 150],
    ["gmm_wi.5", GROUPED + "gmm_wi/pallas_call", 250, 300],
    ["fusion.485", GROUPED + "combine/gather", 550, 170],
    ["fusion.486", GROUPED + "combine/reduce_sum", 720, 30],
    ["row_gather_sum.2", BACKWARD + "combine/pallas_call", 750, 40],
    ["attn.2", "jit(_train_step)/blocks/attn/pallas_call", 790, 200],
    ["fusion.11", BACKWARD + "../router/dot_general", 990, 50],
    ["fusion.12", "jit(_train_step)/blocks/ln_mlp/mul", 1040, 100],
]
TRACE = {"devices": {"/device:TPU:0": {
    "ops": ROWS, "modules": [["jit__train_step(1)", "", 0, 2000]],
}}, "host": []}


def test_moe_row_move_ms_is_the_scatter_and_combine_scopes_kernels_or_not():
    from benchmark import layers, trace_reduce
    from benchmark.readers import scope_ms

    spec = layers.spec("moe_row_move_ms")
    assert spec["reader"] == "scope_ms" and spec["layer"] == "step program"
    match = spec["params"]["match"]
    # scatter 150 + combine 170 + 30 + its kernel 40; not the plan, the
    # grouped GEMM, the router or anything outside moe/
    assert trace_reduce.scope_seconds(ROWS, match) == pytest.approx(390e-9)
    evidence = {"trace": TRACE, "step_module": "train_step"}
    assert scope_ms.read(evidence, spec["params"]) == pytest.approx(390e-6)
    # moe_dispatch_ms leaves the kernel out, which is why this one exists
    dispatch = layers.spec("moe_dispatch_ms")["params"]
    assert scope_ms.read(evidence, dispatch) == pytest.approx(500e-6)
    # a program without these scopes (the capacity einsum) gives nothing
    other = {"devices": {"/device:TPU:0": {
        "ops": [ROWS[7], ROWS[9]],
        "modules": TRACE["devices"]["/device:TPU:0"]["modules"],
    }}, "host": []}
    assert scope_ms.read(dict(evidence, trace=other), spec["params"]) is None


def test_the_manifest_lists_the_reading_for_the_dropless_cells_only():
    from benchmark import build

    entry = [
        m for m in build.manifest()["per_layer"]
        if m["name"] == "moe_row_move_ms"
    ]
    assert entry == [{
        "name": "moe_row_move_ms", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "step program",
        "moves": "tokens_per_s_chip",
        "workloads": [
            "olmoe-1b-7b.train_steady", "joyai-llm-flash.train_steady",
            "nemotron-3-nano-30b-a3b.train_steady",
            "granite-4.0-h-small.train_steady",
        ],
    }]
