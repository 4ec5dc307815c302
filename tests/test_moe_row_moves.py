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


# (tokens, k, D): k 1 / 2 / 8, D lane-aligned and not, T odd; the last is
# whole lanes that a pad to 1024 makes a whole float32 tile: plain rows of
# such a width go through the kernel, padded at its door (under the TPU
# interpreter here)
SHAPES = [(13, 1, 16), (37, 2, 48), (21, 2, 128), (9, 8, 256), (40, 8, 24),
          (21, 2, 640)]


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


@pytest.mark.parametrize("d,k,dtype,width", [
    (2688, 6, jnp.bfloat16, 3072),     # Nemotron-3-Nano: 21 lane tiles
    (2560, 8, jnp.bfloat16, 3072),     # Ling-3.0-flash: 20
    (640, 2, F32, 1024), (896, 1, F32, 1024), (1152, 2, F32, 2048),
    (1024, 8, jnp.bfloat16, 1024),     # 8 lane tiles: a pad of no column
    # whole native tiles need no pad: ``kernel_fits`` answers for them
    (2048, 8, jnp.bfloat16, 0), (4096, 10, jnp.bfloat16, 0), (1024, 8, F32, 0),
    (2048, 64, jnp.bfloat16, 0),
    # no whole lanes
    (1600, 2, jnp.bfloat16, 0), (64, 2, F32, 0), (48, 2, F32, 0),
    # more pad than row
    (128, 2, F32, 0), (384, 2, jnp.bfloat16, 0),
    (2560, 8, F32, 0),                 # a tile of tokens' padded rows: 14 MiB
])
def test_a_row_of_whole_lanes_has_a_padded_width(d, k, dtype, width):
    from dlrover_tpu.ops import row_gather_sum

    assert row_gather_sum.padded_width(d, k, dtype) == width
    # the two answers never both hold, and ``kernel_fits`` kept its own
    assert not (width and row_gather_sum.kernel_fits(d, k, dtype))
    rows, index = jnp.zeros((8, d), dtype), jnp.zeros((4, k), jnp.int32)
    if width:
        with pytest.raises(ValueError, match="kernel_fits"):
            row_gather_sum.gather_sum(rows, index)
        made = jax.eval_shape(row_gather_sum.padded_gather_sum, rows, index)
        assert made.shape == (4, d) and made.dtype == dtype
    else:
        with pytest.raises(ValueError, match="padded_width"):
            row_gather_sum.padded_gather_sum(rows, index)


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
    # Nemotron's and Ling's rows at their padded width, 24 lane tiles
    (3072, 6, jnp.bfloat16, 16),
    (3072, 8, jnp.bfloat16, 16),
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


# -- under a share: only the pairs that have a row are fetched and added ------


def _share_case(t, k, d, blocks, seed=0):
    """Rows whose last is zero and an index that names it for seven pairs of
    eight; of the first two of ``blocks`` tokens a grid step, every pair of
    the first is live and none of the second.  The gates are whole 64ths:
    their products with bfloat16 rows are exact in float32, so a backend
    that fuses a multiply into the add rounds as one that does not."""
    rng = np.random.default_rng(seed)
    n_rows = 200
    rows = rng.standard_normal((n_rows, d)).astype(np.float32)
    rows[-1] = 0
    index = rng.integers(0, n_rows - 1, (t, k))
    dead = rng.random((t, k)) >= 0.125
    dead[:blocks], dead[blocks:2 * blocks] = False, True
    index[dead] = n_rows - 1
    gates = rng.integers(1, 64, (t, k)) / 64
    return (jnp.asarray(rows, jnp.bfloat16), jnp.asarray(index, jnp.int32),
            jnp.asarray(gates, F32), ~dead)


@pytest.mark.parametrize("weighted", [False, True])
@pytest.mark.parametrize("k,d", [(8, 2048), (10, 4096), (6, 2688)])
def test_the_live_only_kernel_is_the_full_fetch_bit_for_bit(
    k, d, weighted, monkeypatch
):
    """JoyAI's and Granite's ``(k, d)``, and Nemotron's through the padded
    call (plain rows of 21 lane tiles, the list made for the 24 they are
    padded to), with an eighth of the pairs live, in
    grid steps of 32 tokens so that a few tokens hold every case: a step
    whose pairs are all live, one with none, a ragged last one (T is no
    multiple of the step; the SMEM block spans several steps), tokens with
    no live row among tokens with some."""
    from jax.experimental.pallas import tpu as pltpu

    from dlrover_tpu.ops import row_gather_sum

    monkeypatch.setattr(row_gather_sum, "_BLOCK_TOKENS", 32)
    t = 32 + 32 + 13
    rows, index, gates, live = _share_case(t, k, d, blocks=32)
    gates = gates if weighted else None
    width = row_gather_sum.padded_width(d, k, rows.dtype)
    assert bool(width) is not row_gather_sum.kernel_fits(d, k, rows.dtype)
    call = (
        row_gather_sum.padded_gather_sum if width
        else row_gather_sum.gather_sum
    )
    listed = row_gather_sum.live_pairs(
        index, rows.shape[0] - 1, width or d, rows.dtype
    )
    kernel = pltpu.InterpretParams()
    full = call(rows, index, gates, interpret=kernel)
    got = call(rows, index, gates, live=listed, interpret=kernel)
    assert got.shape == (t, d) and got.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(got), np.asarray(full))
    np.testing.assert_allclose(
        np.asarray(got, np.float32),
        np.asarray(_definition(rows, index, gates), np.float32),
        rtol=2 ** -7, atol=1e-6,
    )
    none = ~live.any(axis=1)
    assert none[32:64].all() and 0 < none[64:].sum() < 13
    assert not np.asarray(got, np.float32)[none].any()
    assert np.asarray(got, np.float32)[~none].any(axis=1).all()


def test_live_only_sums_in_float32_in_the_order_of_the_choices():
    """256, then ones: the float32 sum of a token's three live rows is 258
    whatever dead pairs stand between them; in bfloat16 the ones are lost."""
    from jax.experimental.pallas import tpu as pltpu

    from dlrover_tpu.ops import row_gather_sum

    rows = jnp.ones((8, 2048), jnp.bfloat16).at[0].set(256).at[7].set(0)
    index = jnp.asarray([[0, 7, 1, 7, 7, 2, 7, 7], [7] * 8], jnp.int32)
    words, count = row_gather_sum.live_pairs(index, 7, 2048, rows.dtype)
    assert list(np.asarray(count)) == [3]
    assert list(np.asarray(words[:3])) == [0, 2, 5]     # token 0's choices
    got = row_gather_sum.gather_sum(
        rows, index, live=(words, count), interpret=pltpu.InterpretParams()
    )
    np.testing.assert_array_equal(np.asarray(got[0], np.float32), 258.0)
    np.testing.assert_array_equal(np.asarray(got[1], np.float32), 0.0)


# sha256 of ``str(jax.make_jaxpr(gather_sum))`` (the kernel's body and its
# grid in it, no file or line) at the parent of the PR that brought the
# live-only kernel: 600 x 8 of 640 bfloat16 rows of 2,048
PARENTS_FULL_FETCH = {
    False: "794023bff98316fe291ab0d98dafaed1fa9924bd46a220174abdcd1e5f67bb8e",
    True: "8cebaa4a028f443ed60f017dc94c9d2d03968ed4200f5bc8036a33e5164ed914",
}


@pytest.mark.parametrize("weighted", [False, True])
def test_with_every_pair_live_the_call_is_the_parents(weighted):
    """Handed no live list (every expert held: OLMoE), ``gather_sum`` traces
    to the program it was before the live-only kernel came, letter for
    letter; handed one it does not."""
    import hashlib

    from dlrover_tpu.ops import row_gather_sum

    args = [
        jax.ShapeDtypeStruct((640, 16, 128), jnp.bfloat16),
        jax.ShapeDtypeStruct((600, 8), jnp.int32),
    ] + [jax.ShapeDtypeStruct((600, 8), F32)] * weighted

    def traced(fn):
        text = str(jax.make_jaxpr(fn)(*args))
        return hashlib.sha256(text.encode()).hexdigest()

    def every_pair(*a):
        return row_gather_sum.gather_sum(*a, interpret=False)

    def listed_pairs(rows, index, *gates):
        live = row_gather_sum.live_pairs(index, 639, 2048, rows.dtype)
        return row_gather_sum.gather_sum(
            rows, index, *gates, live=live, interpret=False
        )

    assert traced(every_pair) == PARENTS_FULL_FETCH[weighted]
    assert traced(listed_pairs) != PARENTS_FULL_FETCH[weighted]


def test_a_live_list_made_for_other_rows_is_refused():
    """The list's grid steps follow from the rows' width and dtype: one made
    for float32 rows (steps of 256 tokens and not 512: as many words, other
    offsets, twice the counts) raises instead of summing other tokens'
    rows, and so does one of another index."""
    from dlrover_tpu.ops import row_gather_sum

    rows = jnp.zeros((64, 16, 128), jnp.bfloat16)
    index = jnp.zeros((1024, 8), jnp.int32)
    good = row_gather_sum.live_pairs(index, 63, 2048, jnp.bfloat16)
    assert [a.shape for a in good] == [(8192,), (2,)]
    made = jax.eval_shape(
        lambda *a: row_gather_sum.gather_sum(*a, live=good), rows, index
    )
    assert made.shape == (1024, 2048)
    for live in (
        row_gather_sum.live_pairs(index, 63, 2048, F32),
        row_gather_sum.live_pairs(index[:512], 63, 2048, jnp.bfloat16),
        (good[0], good[1][:1]),
    ):
        with pytest.raises(ValueError, match="not live_pairs of a 1024 x 8"):
            row_gather_sum.gather_sum(rows, index, live=live)
    # the padded call's steps follow from the width it fetches, 3,072 for
    # rows of 2,688: a list made for narrower rows (steps of 512 tokens,
    # not 256) is another's too
    plain, six = jnp.zeros((64, 2688), jnp.bfloat16), index[:, :6]
    for width, fine in ((3072, True), (1024, False)):
        live = row_gather_sum.live_pairs(six, 63, width, jnp.bfloat16)

        def made():
            return jax.eval_shape(
                lambda *a: row_gather_sum.padded_gather_sum(*a, live=live),
                plain, six,
            )

        if fine:
            assert made().shape == (1024, 2688)
        else:
            with pytest.raises(ValueError, match="rows of 3072 x bfloat16"):
                made()


@pytest.mark.parametrize("d,k,dtype,block", [
    (2048, 8, jnp.bfloat16, 512),      # JoyAI: 4 MiB of float32 sums
    (4096, 10, jnp.bfloat16, 256),     # Granite: 8 MiB would not fit
    (1024, 8, F32, 512),
    # padded to 24 lane tiles: 6 MiB of sums do not fit, 3 do; Nemotron's
    # 256 x 6 indices are no whole SMEM tiles, one block spans two steps
    (3072, 6, jnp.bfloat16, 256),
])
def test_a_live_only_grid_step_is_as_many_tokens_as_its_sums_fit(
    d, k, dtype, block
):
    """... and an SMEM block of indices is whole tiles of 1024 words, over
    two grid steps where one step's are not."""
    from dlrover_tpu.ops import row_gather_sum

    got, span, t_pad = row_gather_sum._live_steps(16000, k, d, dtype)
    assert got == block and span * k % 1024 == 0 and span in (block, 2 * block)
    assert t_pad == 16384
    # fewer tokens than a step: one step, one SMEM block, whole chunks
    chunk = row_gather_sum._chunk_tokens(d, k, dtype)
    assert row_gather_sum._live_steps(40, k, d, dtype) == (
        -(-40 // chunk) * chunk,
    ) * 3


@pytest.mark.parametrize("d", [1024, 640])
def test_a_shares_plan_moves_rows_as_the_xla_form_does(d, monkeypatch):
    """A plan over 2 of 8 experts whose budget is one pair short: through
    ``_tokens_of_rows`` and ``_rows_of_tokens`` with row-tiled rows (the
    live-only kernel) the outputs and every cotangent are those of plain
    rows (XLA's gather and sum), and both calls were handed the list.
    Rows of 640 are whole tiles only padded to 1024: they stay plain, the
    padded call is the kernel's door, and XLA's form is what they take when
    told that no pad fits."""
    from dlrover_tpu.ops import row_gather_sum

    rng = np.random.default_rng(3)
    t, k, held, total, block = 48, 2, 2, 8, 8
    width = row_gather_sum.padded_width(d, k, F32)
    assert width == (1024 if d == 640 else 0)
    door = "padded_gather_sum" if width else "gather_sum"
    gate_idx = jnp.asarray(np.stack([
        rng.choice(total, size=k, replace=False) for _ in range(t)
    ]), jnp.int32)
    here = int(((gate_idx >= 0) & (gate_idx < held)).sum())
    per_expert = [int((gate_idx == e).sum()) for e in range(held)]
    # whole blocks for the first expert, the second's last pair past the end
    first = -(-per_expert[0] // block) * block
    n_pad = first + (per_expert[1] - 1) // block * block + block
    plan = moe._dispatch_plan(gate_idx, held, block, n_pad, 0, total)
    assert int(plan["here"]) == here > int(plan["kept"]) > 0
    dest = np.asarray(plan["dest"])
    assert (dest == n_pad - 1).sum() == t * k - int(plan["kept"])
    plan["live"] = row_gather_sum.live_pairs(
        plan["dest"], n_pad - 1, width or d, F32
    )
    assert int(plan["live"][1].sum()) == int(plan["kept"])

    rows = jnp.asarray(rng.standard_normal((n_pad, d)), F32).at[-block:].set(0)
    x = jnp.asarray(rng.standard_normal((t, d)), F32)
    gates = jnp.asarray(rng.random((t, k)), F32)
    d_out = jnp.asarray(rng.standard_normal((t, d)), F32)
    handed = []
    real = getattr(row_gather_sum, door)

    def seen(rows, index, weights=None, *, live=None):
        assert rows.shape[1:] == ((d,) if width else (d // 128, 128))
        handed.append(live)
        return real(rows, index, weights, live=live)

    def moved(tiled):
        form = (lambda a: a.reshape(a.shape[0], -1, 128)) if tiled else (
            lambda a: a
        )
        out, vjp = jax.vjp(
            lambda r, g: moe._tokens_of_rows(form(r), g, plan), rows, gates
        )
        made, back = jax.vjp(
            lambda x: moe._rows_of_tokens(x, plan, tiled), x
        )
        return (out, *vjp(d_out), made.reshape(n_pad, d),
                *back(form(rows * 0.5 + 1.0).at[-block:].set(0)))

    import unittest.mock

    with unittest.mock.patch.object(row_gather_sum, door, seen):
        tiled = moved(not width)
    assert len(handed) == 2
    for words, count in handed:
        np.testing.assert_array_equal(words, plan["live"][0])
        np.testing.assert_array_equal(count, plan["live"][1])
    monkeypatch.setattr(row_gather_sum, "padded_width", lambda *a: 0)
    for got, want in zip(tiled, moved(False)):
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    assert len(handed) == 2


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


@pytest.mark.parametrize("held,d_model", [(0, 1024), (2, 1024), (2, 640)])
def test_a_layer_whose_rows_are_whole_tiles_runs_them_row_tiled(
    held, d_model, monkeypatch
):
    """d_model 1024 in float32 is a whole tile a row: the layer's rows go
    row-tiled through the GEMMs and the kernel.  Same output and gradients
    as the plain form, which the same layer takes when told nothing fits.
    Holding 2 of its 4 experts the layer hands the kernel the pairs that
    have a row here; holding all it hands none.  d_model 640 is a whole
    tile only padded to 1024: the rows stay plain through the gathers and
    the GEMMs, the padded call takes them as they are, and under a share
    its live list is the one made for the padded width."""
    from dlrover_tpu.ops import row_gather_sum

    width = row_gather_sum.padded_width(d_model, 2, F32)
    assert width == (1024 if d_model == 640 else 0)
    door = "padded_gather_sum" if width else "gather_sum"
    x = jnp.asarray(
        np.random.default_rng(5).standard_normal((2, 12, d_model)), F32
    )
    layer = moe.MoEMlp(
        num_experts=4, d_ff=128, top_k=2, activation="swiglu", dtype=F32,
        param_dtype=F32, dispatch="grouped", gmm_block_rows=8,
        norm_topk_prob=False, experts_held=held, row_budget_multiple=2.0,
    )
    params = layer.init(jax.random.PRNGKey(5), x)
    forms = []
    real = getattr(row_gather_sum, door)

    def seen(rows, index, *args, live=None):
        if live is not None:
            # the list of this index for rows of the width that is fetched
            want = row_gather_sum.live_pairs(
                index, rows.shape[0] - 1, width or d_model, F32
            )
            assert [a.shape for a in live] == [a.shape for a in want]
        forms.append((rows.shape, live is not None))
        return real(rows, index, *args, live=live)

    monkeypatch.setattr(row_gather_sum, door, seen)

    def loss(p, x):
        out, aux = layer.apply(p, x)
        return jnp.sum(out ** 2) + aux

    def value_and_grads():
        # traced anew each time: what is patched above and below is seen
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))(params, x)

    tiled = value_and_grads()
    # combine's forward and the transpose of rows-of-tokens
    n_pad = moe._share_row_budget(48, 8, 2, 4, 2.0) if held else 80
    row = (d_model,) if width else (8, 128)
    assert forms == [((n_pad,) + row, bool(held))] * 2
    monkeypatch.setattr(row_gather_sum, "kernel_fits", lambda *a: False)
    monkeypatch.setattr(row_gather_sum, "padded_width", lambda *a: 0)
    plain = value_and_grads()
    assert len(forms) == 2
    # Holding all, the old case, at the old tolerance.  Holding half, the
    # router kernel's gradient ``[1024, 4]`` (entries up to 171, each a sum
    # over the 24 tokens that cancels) differs in 11 entries of 0.05 to 2.1
    # by at most 4.0e-5, float32 sums in another order; no other leaf
    # passes 1e-5.
    atol = 1e-4 if held else 1e-5
    for got, want in zip(jax.tree.leaves(tiled), jax.tree.leaves(plain)):
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=atol)


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


def test_the_manifest_lists_the_reading_for_the_dropless_cells():
    """By membership, not as a closed set (PERF.md §7 (7)): a later cell
    with the dropless dispatch joins the list."""
    from benchmark import build

    (entry,) = [
        m for m in build.manifest()["per_layer"]
        if m["name"] == "moe_row_move_ms"
    ]
    cells = entry.pop("workloads")
    assert entry == {
        "name": "moe_row_move_ms", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "step program",
        "moves": "tokens_per_s_chip",
    }
    assert {
        "olmoe-1b-7b.train_steady", "joyai-llm-flash.train_steady",
        "nemotron-3-nano-30b-a3b.train_steady",
        "granite-4.0-h-small.train_steady", "lfm2-8b-a1b.train_steady",
    } <= set(cells)
    # never a cell whose experts go through the capacity einsum
    assert "mixtral-8x7b.train_steady" not in cells
