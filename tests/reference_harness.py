"""How a program is held to its plain reference, for every model alike.

Seeded tokens and seeded float32 weights (the program's own init, then the
leaves a model names moved off their initial values so that a fault in them
shows); the per-token loss, the loss and every gradient of the program
against the reference module's ``token_nll`` and ``loss_and_grads``.

What cases share is paid once: a configuration's per-token loss and its
loss-and-gradients are jitted once and their results kept by
(configuration, weights, tokens), so a case that varies only the
reference's side reuses the program's.  Jitted against op by op, no
token's loss moved and a gradient entry by at most 1.7e-6 (read on
Nemotron-H's tiny model); every comparison passes at the tolerances it had.

A model's file gives its ``config``, its reference module, the leaves its
init moves and its tolerances; nothing here names a model.
"""

import dataclasses
import functools

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.models import moe as moe_lib

from dlrover_tpu.models.transformer import TransformerLM


def tokens(seed, batch, seq, vocab):
    """Seeded (inputs, targets), the targets the inputs' next tokens."""
    rows = jax.random.randint(
        jax.random.PRNGKey(seed), (batch, seq + 1), 0, vocab
    )
    return rows[:, :-1], rows[:, 1:]


def init(cfg, inputs, seed=0, move=None):
    """The program's own init under ``seed``; then ``move(name, leaf, draw)``
    for every leaf, where ``draw(shape)`` is the next seeded normal sample,
    returns the leaf moved off its initial value (or as it was)."""
    params = nn.meta.unbox(
        jax.jit(TransformerLM(cfg).init)(jax.random.PRNGKey(seed), inputs)
    )["params"]
    if move is None:
        return params
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 100), 1000))

    def draw(shape):
        return jax.random.normal(next(keys), shape)

    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: move(jax.tree_util.keystr(path), leaf, draw),
        params,
    )


def held(params, cfg):
    """``params`` with each stacked expert layer's ``wi`` / ``wg`` / ``wo``
    cut to the config's share of the experts."""
    first, count = cfg.first_expert, cfg.resolved_experts_held

    def cut(path, leaf):
        name = jax.tree_util.keystr(path)
        if any(f"['moe']['{w}']" in name for w in ("wi", "wg", "wo")):
            return leaf[:, first:first + count]
        return leaf

    return jax.tree_util.tree_map_with_path(cut, params)


def token_nll(logits, targets):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], -1)[..., 0]


def program_outputs(cfg, params, inputs, targets):
    """(per-token nll, the auxiliary term, the MTP module's per-token nll
    or None where the configuration has no such module)."""
    model = TransformerLM(cfg)
    with jax.default_matmul_precision("highest"):
        if cfg.mtp_depth:
            logits, aux, mtp = model.apply(
                {"params": params}, inputs, next_tokens=targets
            )
            return (token_nll(logits, targets), aux,
                    token_nll(mtp[:, :-1], targets[:, 1:]))
        logits, aux = model.apply({"params": params}, inputs)
        return token_nll(logits, targets), aux, None


def program_nll(cfg, params, inputs, targets):
    return program_outputs(cfg, params, inputs, targets)[0]


def program_loss(cfg, params, inputs, targets):
    """What the step trains: the mean nll, the auxiliary term and the
    weighted MTP term; the parts ride along."""
    nll, aux, mtp = program_outputs(cfg, params, inputs, targets)
    loss = nll.mean() + aux
    if mtp is not None:
        loss = loss + cfg.mtp_weight * mtp.mean()
    return loss, (nll, aux, mtp)


@functools.cache
def _jitted(what, cfg):
    fn = {
        "outputs": program_outputs,
        "grads": jax.value_and_grad(program_loss, argnums=1, has_aux=True),
    }[what]
    return jax.jit(functools.partial(fn, cfg))


class Harness:
    """One model's program against ``ref``, at that model's tolerances:
    ``loss_atol`` the loss, a gradient leaf within ``grad_atol + grad_rtol
    x`` the reference leaf's largest entry (a token's loss is the file's to
    bound: ``nll_gap`` hands it the distance).  Leaves
    whose name holds one of ``no_gradient`` must get none (a bias that
    picks and never weighs), every other one some, but for ``may_be_zero``."""

    def __init__(self, ref, loss_atol, grad_atol, grad_rtol,
                 no_gradient=(), may_be_zero=()):
        self.ref, self.loss_atol = ref, loss_atol
        self.grad_atol, self.grad_rtol = grad_atol, grad_rtol
        self.no_gradient, self.may_be_zero = no_gradient, may_be_zero
        self._kept = {}

    def _once(self, what, cfg, params, tokens):
        # keyed by identity: the entry holds its weights and tokens, so an
        # id is never another tree's
        key = (what, cfg, id(params), id(tokens))
        if key not in self._kept:
            self._kept[key] = (
                params, tokens, _jitted(what, cfg)(params, *tokens)
            )
        return self._kept[key][2]

    def outputs(self, cfg, params, tokens):
        """The forward's (nll, aux, mtp nll): that of the loss-and-gradients
        program where a case has run it already (a file puts its gradient
        cases first), one more program otherwise."""
        if ("grads", cfg, id(params), id(tokens)) in self._kept:
            return self.loss_and_grads(cfg, params, tokens)[1]
        return self._once("outputs", cfg, params, tokens)

    def nll(self, cfg, params, tokens):
        return self.outputs(cfg, params, tokens)[0]

    def loss_and_grads(self, cfg, params, tokens):
        (loss, parts), grads = self._once("grads", cfg, params, tokens)
        return loss, parts, grads

    def nll_gap(self, cfg, params, tokens, ref_cfg=None, **reference_kw):
        """The largest distance of a token's loss from the reference's,
        computed under ``ref_cfg`` (the program's by default) and whatever
        fault ``reference_kw`` names."""
        want = self.ref.token_nll(
            dataclasses.asdict(ref_cfg or cfg), params, *tokens,
            **reference_kw,
        )
        return float(jnp.abs(self.nll(cfg, params, tokens) - want).max())

    def loss_and_every_gradient_match(self, cfg, params, tokens):
        got, _, got_grads = self.loss_and_grads(cfg, params, tokens)
        # the reference's side as one program too (op by op it compiles
        # every token-by-token piece of itself for each of its calls)
        want, want_grads = jax.jit(functools.partial(
            self.ref.loss_and_grads, dataclasses.asdict(cfg)
        ))(params, *tokens)
        assert abs(float(got) - float(want)) <= self.loss_atol
        flat_got = jax.tree_util.tree_leaves_with_path(got_grads)
        flat_want = jax.tree_util.tree_leaves(want_grads)
        assert len(flat_got) == len(flat_want)
        for (path, g), w in zip(flat_got, flat_want):
            name = jax.tree_util.keystr(path)
            top = float(jnp.abs(w).max())
            bound = self.grad_atol + self.grad_rtol * top
            assert float(jnp.abs(g - w).max()) <= bound, name
            if any(part in name for part in self.no_gradient):
                assert top == 0 and not jnp.asarray(g).any(), name
            elif not any(part in name for part in self.may_be_zero):
                assert top > 0, name


def shares_add_up(ref, fields, n, whole, held_here, layer_of, shared, atol,
                  balance=None):
    """The routed parts of all the shares of ``held_here`` experts of one
    expert layer (``layer_of(first)`` the program's layer holding the
    experts from ``first``, each applied as one program), plus the shared
    expert's output ``shared`` counted once, are the uncut reference's
    layer; nothing is dropped, each share's routed part is the reference's
    own partial sum, and the shares' pairs add up to all of them.
    ``balance(aux, reference's term)`` checks a share's auxiliary term."""
    with jax.default_matmul_precision("highest"):
        want, _ = ref.expert_layer(fields, n, whole)
        got, seen = shared, 0.0
        for first in range(0, fields["num_experts"], held_here):
            layer = layer_of(first)
            part = dict(whole, **{
                w: whole[w][first:first + held_here]
                for w in ("wi", "wg", "wo") if w in whole
            })
            (out, aux), sown = jax.jit(lambda part, n: layer.apply(
                {"params": part}, n, mutable=["intermediates"]
            ))(part, n)
            stats = sown["intermediates"]
            assert float(moe_lib.split_stats(stats["moe_stats"][0])[1]) == 0.0
            seen += float(stats[moe_lib.SHARE_STATS_NAME][0][0])
            # what every chip computes alike is counted once
            got = got + (out - shared)
            ours, term = ref.routed_part(
                dict(fields, first_expert=first), n, part
            )
            np.testing.assert_allclose(out - shared, ours, atol=atol)
            if balance is not None:
                balance(aux, term)
    np.testing.assert_allclose(got, want, atol=atol)
    assert seen == pytest.approx(1.0)
