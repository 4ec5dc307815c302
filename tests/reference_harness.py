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

The step programs are built here too, once a process.  ``built`` keys on
the configuration (a frozen dataclass: every field), the batch and the
sequence length, the number of devices and the mesh's axes, the optimizer
and its rate, and whatever further options of ``build_sharded_train`` a
case names; it builds under the key ``train_lib``'s own build cache names
the program by, so the cache holds it and nothing stands beside it.
``preset`` reads a tiny preset of the benchmark, ``lowered`` lowers a built
step once for every case that reads its text, ``first_step`` runs one step
on given weights, ``digest`` folds a state as the trainer does,
``trained`` is a trainer of the configuration on one device: everything it
records while it is built and the step it compiled, ``compile_event`` what
it says of that step; ``fit`` a trainer on every device, trained.
A file is one process: cases
that read the same program belong in the same file (and when a file ends
``conftest.py`` drops every program jax compiled: what ``built`` keeps
compiles anew if a later file of the same worker asks for it).

What ``built``, ``lowered`` and ``compile_event`` hand out is shared by
every later case of the file: a case never mutates it (no attribute of the
``ShardedTrain`` set, no entry of the event's dictionary changed, no
``reset_build_cache()`` while another case still counts on a hit).  State
is never shared: ``first_step`` and a case's own ``train.init`` make a new
one each time, copied from the weights they are given, so the step's
donation of its state takes nothing of another case's.
"""

import contextlib
import dataclasses
import functools
import os

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.common import telemetry
from dlrover_tpu.models import moe as moe_lib
from dlrover_tpu.models.transformer import TransformerLM
from dlrover_tpu.parallel import rules as lr
from dlrover_tpu.runtime import compile_cache
from dlrover_tpu.runtime.mesh import ParallelConfig, build_mesh
from dlrover_tpu.trainer import train_lib

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- the step programs, built once a process ----------------------------------


@functools.cache
def preset(name, seq=None):
    """``(model, seq, sequences a chip)`` of the benchmark's tiny preset
    ``name``, at ``seq`` tokens or its own."""
    from benchmark import build

    cfg = build.load_json(os.path.join(
        REPO, "tests", "benchmark_suite", "presets", f"{name}.json"
    ))
    seq = seq or cfg["run"]["seq_len"]
    return (
        build.transformer_config(build.model_group(cfg), seq), seq,
        cfg["run"]["sequences_per_chip"],
    )


def built(cfg, *, batch, seq, devices=1, parallel=None, optimizer="adafactor",
          learning_rate=1e-3, **engine):
    """``build_sharded_train``'s program of ``cfg`` on the first ``devices``
    host devices (``parallel``: the mesh's axes, all of them ``data`` by
    default), kept for the process; ``engine``: further options of
    ``build_sharded_train``."""
    return _built(
        cfg, batch, seq, devices, tuple(sorted((parallel or {}).items())),
        optimizer, learning_rate, tuple(sorted(engine.items())),
    )


@functools.cache
def _built(cfg, batch, seq, devices, parallel, optimizer, learning_rate,
           engine):
    mesh = build_mesh(
        ParallelConfig(**(dict(parallel) or {"data": -1})),
        devices=jax.devices()[:devices],
    )
    engine = dict(engine)
    key = compile_cache.train_cache_key(
        cfg, mesh.devices.shape, global_batch_size=batch, seq_len=seq,
        optimizer=f"{optimizer}/lr={learning_rate!r}", **engine,
    )
    return train_lib.build_sharded_train(
        TransformerLM(cfg),
        train_lib.make_optimizer(optimizer, learning_rate=learning_rate),
        mesh, lr.DEFAULT_RULES, global_batch_size=batch, seq_len=seq,
        cache_key=key, **engine,
    )


_LOWERED = {}


def lowered(train):
    """The step of ``train`` lowered for its own abstract state and batch:
    one tracing for every case that reads its text or compiles it."""
    # by identity (a ``ShardedTrain`` does not hash): the entry holds the
    # program, so an id is never another's
    if id(train) not in _LOWERED:
        state = jax.eval_shape(train.init_fn, train_lib._ABSTRACT_KEY)
        with train_lib.use_mesh(train.mesh):
            _LOWERED[id(train)] = (
                train, train.step_fn.lower(state, train.batch_avals)
            )
    return _LOWERED[id(train)][1]


def first_step(train, params, tokens):
    """``(the new state, the metrics)`` of one step of ``train`` from a new
    state that holds copies of ``params``, on the batch ``tokens``."""
    state = train.init(jax.random.PRNGKey(0))
    state = state.replace(params=jax.tree.map(
        lambda new, old: jax.device_put(
            jnp.array(new, old.dtype, copy=True), old.sharding
        ), params, state.params,
    ))
    batch = {"inputs": np.asarray(tokens[0]), "targets": np.asarray(tokens[1])}
    return train.step(state, train_lib.shard_batch(batch, train))


def router_biases(tree):
    """{path: the router bias there, on the host} of a parameter tree."""
    return {
        "/".join(k.key for k in path): np.asarray(leaf)
        for path, leaf in jax.tree_util.tree_leaves_with_path(tree)
        if path[-1].key == "router_bias"
    }


def digest(state):
    """The train state's digest as the trainer takes it, one program a
    tree (op by op a leaf's fold is a program for every shape)."""
    from dlrover_tpu.trainer import state_digest

    return int(jax.jit(state_digest._digest_tree)(state))


@contextlib.contextmanager
def _recording():
    """The tap of everything the process-wide recorder records inside."""
    recorder = telemetry.recorder()
    was_enabled = recorder.enabled
    recorder.configure(enabled=True)
    try:
        with recorder.open_tap() as tap:
            yield tap
    finally:
        recorder.configure(enabled=was_enabled)


def compile_event(cfg, seq, batch=1, patches=()):
    """The attributes of the ``compile`` event of ``trained``'s trainer."""
    (event,) = [
        e for e in trained(cfg, seq, batch, patches)[0] if e[0] == "compile"
    ]
    return event[-1]


def trained(cfg, seq, batch=1, patches=()):
    """``(events, train)`` of an ``ElasticTrainer`` of ``cfg`` on the FIRST
    host device, ``batch`` sequences a step: every event it records while
    it is built (its start-up spans, the ``compile`` event and its
    children) and its compiled ``ShardedTrain``, which is the program
    ``built(cfg, batch=batch, seq=seq)`` would build (the same lowered
    text: the pinned hashes of ``tests/test_step_scopes.py`` read either),
    so a case that wants a preset's event AND its step walks the model
    once.  ``patches``: (object, attribute, value) triples in force while
    it is built (the build cache is emptied around such a build, and only
    around such a one)."""
    return _trained(cfg, seq, batch, tuple(patches))


@functools.cache
def _trained(cfg, seq, batch, patches):
    from dlrover_tpu.trainer import elastic_trainer

    try:
        with pytest.MonkeyPatch.context() as patch, _recording() as tap:
            # the trainer takes every device there is: hand it one
            patch.setattr(
                elastic_trainer, "build_mesh",
                lambda parallel: build_mesh(
                    parallel, devices=jax.devices()[:1]
                ),
            )
            for target, name, value in patches:
                patch.setattr(target, name, value)
            if patches:
                # a patch changes the program and not the key it is kept by
                train_lib.reset_build_cache()
            trainer = elastic_trainer.ElasticTrainer(
                cfg, elastic_trainer.TrainerConfig(
                    global_batch_size=batch, seq_len=seq, world=1,
                    optimizer="adafactor", warmup_compile=True,
                    ckpt_every=1000,
                ), client=None,
            )
            return tuple(tap.take()), trainer.train
    finally:
        if patches:
            train_lib.reset_build_cache()


def batches(n, batch, seq, vocab, seed=0):
    """``n`` seeded batches of ``batch`` rows of ``seq`` tokens and their
    next tokens."""
    rows = np.random.default_rng(seed).integers(
        0, vocab, (n, batch, seq + 1), dtype=np.int32
    )
    return [{"inputs": r[:, :-1], "targets": r[:, 1:]} for r in rows]


def fit(cfg, directory, *, seq, steps=10, batch=None, seed=0, **options):
    """An ``ElasticTrainer`` of ``cfg`` on every host device, trained to step
    ``steps`` on seeded batches (``batch`` rows, one a device by default)
    under the trainer options a system file runs (Adafactor at 1e-2, a
    report every 5 steps read 4 steps late, the step compiled before the
    first batch, no save) and whatever ``options`` name; with ``ckpt_every``
    among them it saves under ``directory`` and starts from what it finds
    there.  Returns, after the trainer is closed: ``train`` (its
    ``ShardedTrain``: a case that wants the step program runs or lowers THIS
    one, and a later trainer of the same key is handed it by the build
    cache), ``grad_accum``, ``taken`` (everything the recorder took),
    ``seen`` (step: its metrics), ``began`` and ``ended`` ((step, the state's
    digest) before the first step and after the last), ``params`` (the
    weights before and after, on the host) and ``traces`` (how often
    ``train_step`` was traced meanwhile: 0 where the build cache had the
    program)."""
    from dlrover_tpu.trainer.elastic_trainer import (
        ElasticTrainer, TrainerConfig,
    )

    batch = batch or jax.device_count()
    if "ckpt_every" in options:
        options["checkpoint_dir"] = os.path.join(directory, "ckpt")
    config = TrainerConfig(**{**dict(
        global_batch_size=batch, seq_len=seq, learning_rate=1e-2,
        optimizer="adafactor", ckpt_every=1000, report_every=5,
        metrics_lag=4, warmup_compile=True,
    ), **options})
    seen, traces = {}, train_lib.trace_count("train_step")
    with pytest.MonkeyPatch.context() as patch, _recording() as tap:
        # the arena outlives processes and is named by the job tag: a tag
        # of this directory's own, or an earlier run's arena is what
        # restores
        patch.setenv(
            "DLROVER_TPU_JOB",
            f"fit{os.getpid()}_{os.path.basename(directory)}",
        )
        patch.setenv(
            "DLROVER_TPU_SOCKET_DIR", os.path.join(directory, "socks")
        )
        trainer = ElasticTrainer(cfg, config, client=None)
        try:
            began = (trainer.step, digest(trainer.state))
            first = jax.tree.map(np.asarray, trainer.state.params)
            trainer.fit(
                batches(steps, batch, seq, cfg.vocab_size, seed),
                max_steps=steps,
                on_step=lambda step, metrics: seen.update({step: metrics}),
            )
            return dict(
                train=trainer.train, grad_accum=trainer.grad_accum,
                taken=tap.take(), seen=seen, began=began,
                ended=(trainer.step, digest(trainer.state)),
                params=(
                    first, jax.tree.map(np.asarray, trainer.state.params)
                ),
                traces=train_lib.trace_count("train_step") - traces,
            )
        finally:
            trainer.close()


# -- a program against its reference -------------------------------------------


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


def pallas_calls(jaxpr):
    """The ``name`` of every ``pallas_call`` equation of ``jaxpr``, those of
    its nested programs too (a scanned body counts once).  Not a count of
    its text: the printer writes a program two equations share once."""
    names = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            names.append(eqn.params["name"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            names.extend(pallas_calls(sub))
    return names


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


# The fields that say HOW the program computes and not what: no reference
# module reads them (it has one way), so two configurations that differ in
# them alone (a model's ``share`` and ``flash`` cases) share the reference's
# programs.
_HOW = (
    "attention_impl", "flash_block_q", "flash_block_kv", "remat", "ssm_impl",
    "fused_ln",
)


def _reference(fn, cfg, kw):
    """``fn(cfg's fields, params, *tokens, **kw)`` of a reference module as
    one program (op by op the glue between its layers, a slice of every
    stacked leaf for every layer, is a program each)."""
    how = {f.name: f.default for f in dataclasses.fields(cfg) if f.name in _HOW}
    return _reference_program(fn, dataclasses.replace(cfg, **how), kw)


@functools.cache
def _reference_program(fn, cfg, kw):
    return jax.jit(functools.partial(fn, dataclasses.asdict(cfg), **dict(kw)))


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

    def gradient_program(self, cfg, params, tokens):
        """The jaxpr of the loss-and-gradients program (the tracing
        ``loss_and_grads`` made, where it has run)."""
        return _jitted("grads", cfg).trace(params, *tokens).jaxpr

    def reference(self, what, cfg, params, tokens, **kw):
        """The reference module's ``what`` (``forward``, ``token_nll``,
        ``loss_and_grads``) under ``cfg``'s fields and whatever ``kw`` names
        (a fault, a lowered precision: structure, so a program each), as
        NumPy arrays; compiled once for a (``what``, ``cfg``, ``kw``)."""
        fn = _reference(getattr(self.ref, what), cfg, tuple(sorted(kw.items())))
        return jax.tree.map(np.asarray, fn(params, *tokens))

    def nll_gap(self, cfg, params, tokens, ref_cfg=None, **reference_kw):
        """The largest distance of a token's loss from the reference's,
        computed under ``ref_cfg`` (the program's by default) and whatever
        fault ``reference_kw`` names."""
        want = self.reference(
            "token_nll", ref_cfg or cfg, params, tokens, **reference_kw
        )
        got = np.asarray(self.nll(cfg, params, tokens))
        return float(np.abs(got - want).max())

    def loss_and_every_gradient_match(self, cfg, params, tokens):
        got, _, got_grads = self.loss_and_grads(cfg, params, tokens)
        want, want_grads = self.reference(
            "loss_and_grads", cfg, params, tokens
        )
        assert abs(float(got) - float(want)) <= self.loss_atol
        flat_got = jax.tree_util.tree_leaves_with_path(got_grads)
        flat_want = jax.tree_util.tree_leaves(want_grads)
        assert len(flat_got) == len(flat_want)
        # compared on the host: a leaf's ``abs``, ``max`` and difference
        # are a program each for every shape a tree has
        for (path, g), w in zip(flat_got, flat_want):
            name, g = jax.tree_util.keystr(path), np.asarray(g)
            top = float(np.abs(w).max())
            bound = self.grad_atol + self.grad_rtol * top
            assert float(np.abs(g - w).max()) <= bound, name
            if any(part in name for part in self.no_gradient):
                assert top == 0 and not g.any(), name
            elif not any(part in name for part in self.may_be_zero):
                assert top > 0, name


def share_program(layer_of):
    """``apply(part, n, first)``, the ``((output, aux), sown)`` of the share
    ``layer_of(first)`` of an expert layer on the weights ``part``: ONE
    program for every share, the share's place an argument of it (a
    constant of it, the layer is walked once a share: 32 walks of GLM-5.2's
    layer).  ``check_share`` reads the place as a Python number, so it is
    asked before the program and not inside it."""
    check = moe_lib.check_share

    @jax.jit
    def traced(part, n, first):
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(moe_lib, "check_share", lambda *args: None)
            return layer_of(first).apply(
                {"params": part}, n, mutable=["intermediates"]
            )

    def apply(part, n, first):
        layer = layer_of(first)
        check(layer.num_experts, layer.experts_held, first, layer.dispatch)
        return traced(part, n, first)

    return apply


def shares_add_up(ref, fields, n, whole, held_here, layer_of, shared, atol,
                  balance=None):
    """The routed parts of all the shares of ``held_here`` experts of one
    expert layer (``layer_of(first)`` the program's layer holding the
    experts from ``first``, each applied as one program), plus the shared
    expert's output ``shared`` counted once, are the uncut reference's
    layer; nothing is dropped, each share's routed part is the reference's
    own partial sum, and the shares' pairs add up to all of them.
    ``balance(aux, reference's term)`` checks a share's auxiliary term."""
    apply = share_program(layer_of)
    with jax.default_matmul_precision("highest"):
        want, _ = ref.expert_layer(fields, n, whole)
        got, seen = shared, 0.0
        for first in range(0, fields["num_experts"], held_here):
            part = dict(whole, **{
                w: whole[w][first:first + held_here]
                for w in ("wi", "wg", "wo") if w in whole
            })
            (out, aux), sown = apply(part, n, first)
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
