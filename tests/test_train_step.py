"""The compiled train step, held by its own contract (ISSUE 30).

``training.data_parallel_train_step``, ``training.zero_train_setup`` and
``parallel.sharded.make_sharded_train_step`` each build ONE program; what
that program must compute is stated here against references that share no
code with it: ``jax.value_and_grad`` plus the same optax optimizer on one
device over the whole batch.  Tiny float32 models on the 8 virtual CPU
devices, two steps each.

Float32 tolerances.  A mesh of N devices sums N partial means where one
device sums the whole batch, so gradients differ in the last bits
(relative 1e-6 at worst here).  ``sgd`` passes that on scaled by the
learning rate: ``SGD_ATOL``.  ``adamw`` divides by ``sqrt(v) + eps``, which
for a gradient near zero turns last-bit noise into a visible fraction of
one learning-rate-sized update: ``ADAMW_ATOL`` is a hundredth of the
update (lr 1e-2), where a wrong gradient moves parameters by whole updates.

Then what the step asks of the compiler (ISSUE 25): the asynchronous
all-reduce options, and the reader of a compiled schedule.
"""

import inspect
import types

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu import models, training
from horovod_tpu.common.topology import WORLD_AXIS
from horovod_tpu.compression import DcnCompression
from horovod_tpu.models.transformer import Transformer, gpt_tiny
from horovod_tpu.ops import spmd_ops
from horovod_tpu.ops.comm_model import compiled_collective_counts
from horovod_tpu.parallel import sharded as sh

SGD_ATOL = 1e-6
ADAMW_ATOL = 1e-4
LOSS_RTOL = 1e-6

_MAKE_OPT = {"sgd": lambda: optax.sgd(0.1), "adamw": lambda: optax.adamw(1e-2)}
OPTIMIZERS = [
    pytest.param(_MAKE_OPT["sgd"], SGD_ATOL, id="sgd"),
    pytest.param(_MAKE_OPT["adamw"], ADAMW_ATOL, id="adamw"),
]


def _tree_max_diff(a, b):
    return max(
        float(np.abs(np.asarray(x) - np.asarray(y)).max())
        for x, y in zip(jax.tree_util.tree_leaves(a),
                        jax.tree_util.tree_leaves(b)))


def _tree_bit_equal(a, b):
    return all(
        (np.asarray(x) == np.asarray(y)).all()
        for x, y in zip(
            jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
        )
    )


def _world_mesh(n):
    return Mesh(np.array(jax.devices()[:n]), (WORLD_AXIS,))


class Mlp(nn.Module):
    @nn.compact
    def __call__(self, x):
        return nn.Dense(4)(nn.relu(nn.Dense(16)(x)))


def _lm():
    """gpt_tiny in float32 (``dot`` attention), 8 rows of 16 tokens."""
    model = Transformer(gpt_tiny(dtype=jnp.float32))
    tokens = jax.random.randint(jax.random.PRNGKey(0), (8, 16), 0, 256)
    targets = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, 256)
    return model, tokens, targets


def _mlp():
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 8), jnp.float32)
    y = jax.random.randint(jax.random.PRNGKey(1), (8,), 0, 4)
    return Mlp(), x, y


MODELS = [pytest.param(_lm, id="gpt_tiny"), pytest.param(_mlp, id="mlp")]


def _one_device(model, optimizer, params, x, y, grad_scale=1.0):
    """The reference: two steps of ``jax.value_and_grad`` and the optax
    optimizer on one device over the whole batch.  Returns (params,
    losses)."""
    opt_state = optimizer.init(params)

    @jax.jit
    def one(params, opt_state):
        loss, grads = jax.value_and_grad(
            lambda p: training.softmax_cross_entropy(
                model.apply({"params": p}, x), y))(params)
        grads = jax.tree_util.tree_map(lambda g: g * grad_scale, grads)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    losses = []
    for _ in range(2):
        params, opt_state, loss = one(params, opt_state)
        losses.append(float(loss))
    return params, losses


_initial = {}


def _replicated(model, optimizer, mesh, x, jit_init=True, **kw):
    """create_train_state -> replicate_state -> data_parallel_train_step.
    Each of this file's three models is initialised once (seed 0; under
    ``jit``: op by op the tiny ResNet takes 8 s) and kept on the host;
    every use gets its own copy (the step donates what it is given) and
    its own optimizer state.  ``jit_init=False`` initialises op by op,
    as ``zero_train_setup`` does: the same parameters to the bit."""
    key = (type(model).__name__, jit_init)
    if key not in _initial:
        def create(rng, sample):
            return training.create_train_state(
                model, optax.sgd(0.1), rng, sample)

        _initial[key] = jax.device_get(
            (jax.jit(create) if jit_init else create)(
                jax.random.PRNGKey(0), x[:1]))
    first = jax.tree_util.tree_map(np.array, _initial[key])
    state = training.replicate_state(
        first.replace(opt_state=optimizer.init(first.params)), mesh)
    return state, training.data_parallel_train_step(
        model, optimizer, mesh=mesh, **kw)


def _copy(tree):
    return jax.tree_util.tree_map(jnp.copy, tree)


# -- N devices == one device on the whole batch ------------------------------


_one_device_runs = {}


@pytest.mark.parametrize("world", [1, 2, 8])
@pytest.mark.parametrize("make_opt,atol", OPTIMIZERS)
@pytest.mark.parametrize("make", MODELS)
def test_step_on_n_devices_equals_one_device_on_the_whole_batch(
        request, make, make_opt, atol, world):
    model, x, y = make()
    state, step = _replicated(model, make_opt(), _world_mesh(world), x)
    # one reference run a (model, optimizer): every world starts from the
    # same seed, so from the same parameters
    key = request.node.callspec.id.rsplit("-", 1)[0]
    if key not in _one_device_runs:
        _one_device_runs[key] = _one_device(
            model, make_opt(), jax.device_get(state.params), x, y)
    want_params, want_losses = _one_device_runs[key]
    losses = []
    for _ in range(2):
        state, loss = step(state, x, y)
        losses.append(float(loss))
    np.testing.assert_allclose(losses, want_losses, rtol=LOSS_RTOL)
    assert _tree_max_diff(state.params, want_params) <= atol


@pytest.mark.parametrize("world", [2, 8])
@pytest.mark.parametrize("make_opt,atol", OPTIMIZERS)
def test_sum_is_the_world_s_multiple_of_average(make_opt, atol, world):
    """``op=Sum`` hands the optimizer ``world`` times the gradient that
    ``Average`` does (the loss that is returned stays the mean)."""
    model, x, y = _mlp()
    mesh = _world_mesh(world)
    state, step_sum = _replicated(model, make_opt(), mesh, x, op=hvd.Sum)
    _, step_avg = _replicated(model, make_opt(), mesh, x)
    start = jax.device_get(state.params)
    one_sum, loss_sum = step_sum(_copy(state), x, y)
    one_avg, loss_avg = step_avg(_copy(state), x, y)
    assert float(loss_sum) == float(loss_avg)
    if hasattr(one_sum.opt_state[0], "mu"):
        # Adam's first moment after one step is (1 - b1) x the gradient
        got, base = one_sum.opt_state[0].mu, one_avg.opt_state[0].mu
    else:
        # sgd's first step is -lr x the gradient (read back as a
        # difference of parameters: good to an ulp of the parameter)
        got, base = (jax.tree_util.tree_map(
            lambda p, p0: np.asarray(p) - p0, s.params, start)
            for s in (one_sum, one_avg))
    for g, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(base)):
        np.testing.assert_allclose(np.asarray(g), world * np.asarray(b),
                                   rtol=1e-5, atol=5e-7)
    # and two steps equal one device with the gradient scaled by world
    want_params, _ = _one_device(model, make_opt(), start, x, y,
                                 grad_scale=float(world))
    state, _ = step_sum(state, x, y)
    state, _ = step_sum(state, x, y)
    assert _tree_max_diff(state.params, want_params) <= atol * world


# -- a model with batch_stats -------------------------------------------------


@pytest.fixture(scope="module")
def resnet_step():
    """Two steps of the tiny ResNet over 4 devices, and the same two steps
    replica by replica on one device: each replica's own forward and
    backward on its own rows from the shared state, then the mean."""
    world = 4
    model = models.ResNetTiny(num_classes=10, dtype=jnp.float32)
    optimizer = optax.sgd(0.1)
    images = jax.random.normal(jax.random.PRNGKey(0), (8, 16, 16, 3))
    labels = jax.random.randint(jax.random.PRNGKey(1), (8,), 0, 10)
    state, step = _replicated(model, optimizer, _world_mesh(world), images)
    params = jax.device_get(state.params)
    stats = jax.device_get(state.batch_stats)
    opt_state = optimizer.init(params)

    @jax.jit
    def replica(params, stats, xs, ys):
        def loss_of(p):
            logits, updates = model.apply(
                {"params": p, "batch_stats": stats}, xs,
                mutable=["batch_stats"])
            return (training.softmax_cross_entropy(logits, ys),
                    updates["batch_stats"])

        (_, new_stats), grads = jax.value_and_grad(
            loss_of, has_aux=True)(params)
        return new_stats, grads

    def mean(trees):
        return jax.tree_util.tree_map(
            lambda *xs: sum(xs) / len(xs), *trees)

    rows = images.shape[0] // world
    own_stats = None
    for _ in range(2):
        outs = [replica(params, stats, images[r * rows:(r + 1) * rows],
                        labels[r * rows:(r + 1) * rows])
                for r in range(world)]
        own_stats = [o[0] for o in outs]
        stats = mean(own_stats)
        updates, opt_state = optimizer.update(
            mean([o[1] for o in outs]), opt_state, params)
        params = optax.apply_updates(params, updates)
        state, _ = step(state, images, labels)
    return state, params, stats, own_stats


def test_new_running_statistics_are_the_mean_of_the_replicas_own(
        resnet_step):
    state, _, want_stats, own_stats = resnet_step
    assert _tree_max_diff(state.batch_stats, want_stats) <= 1e-6
    # the replicas' own statistics differ: the mean is not any one's
    assert _tree_max_diff(own_stats[0], own_stats[1]) > 1e-3


def test_batch_stats_update_is_the_mean_of_the_replicas_gradients(
        resnet_step):
    state, want_params, _, _ = resnet_step
    assert _tree_max_diff(state.params, want_params) <= 1e-5


# -- ZeRO == replicated --------------------------------------------------------

# The ZeRO step reduce-scatters where the replicated step all-reduces, and
# XLA:CPU associates the two sums differently: gradients agree to an ulp,
# not to the bit (ISSUE 30 expected sgd bit-equal; measured 1.2e-7 after
# two steps, one ulp of the largest parameters).  adamw's moment updates
# also carry an fma that is contracted differently between
# globally-different programs (first seen in PR 11): measured 8.9e-7.
# A step that drops or mis-scales the exchange moves parameters by 1e-2.
ZERO_SGD_BOUND = 2.5e-7
_ZERO_BOUNDS = [
    pytest.param("sgd", ZERO_SGD_BOUND, id="sgd"),
    pytest.param("adamw", 2e-6, id="adamw"),
]
_replicated_runs = {}


def _replicated_two_steps(opt):
    """(start params, params, loss) of two replicated steps of the tiny LM
    over the world mesh; one run an optimizer for the whole module."""
    if opt not in _replicated_runs:
        model, tokens, targets = _lm()
        state, step = _replicated(model, _MAKE_OPT[opt](), hvd.world_mesh(),
                                  tokens, jit_init=False)
        start = jax.device_get(state.params)
        for _ in range(2):
            state, loss = step(state, tokens, targets)
        _replicated_runs[opt] = (start, jax.device_get(state.params),
                                 float(loss))
    return _replicated_runs[opt]


def _zero_two_steps(opt, monkeypatch, hierarchical, **kw):
    model, tokens, targets = _lm()
    mesh = None
    if hierarchical:
        monkeypatch.setenv("HVD_TPU_SLICE_SIZE", "4")
        mesh = hvd.common.basics._require_init().topology.hierarchical_mesh()
    state, step, _ = training.zero_train_setup(
        model, _MAKE_OPT[opt](), jax.random.PRNGKey(0), tokens[:1],
        hierarchical=hierarchical, mesh=mesh, **kw)
    assert _tree_bit_equal(state.params, _replicated_two_steps(opt)[0])
    for _ in range(2):
        state, loss = step(state, tokens, targets)
    return state, float(loss)


@pytest.mark.parametrize("hierarchical", [False, True],
                         ids=["flat", "hierarchical"])
@pytest.mark.parametrize("opt,bound", _ZERO_BOUNDS)
def test_zero_step_equals_the_replicated_step(
        monkeypatch, opt, bound, hierarchical):
    _, want_params, want_loss = _replicated_two_steps(opt)
    state, loss = _zero_two_steps(opt, monkeypatch, hierarchical)
    np.testing.assert_allclose(loss, want_loss, rtol=LOSS_RTOL)
    assert _tree_max_diff(state.params, want_params) <= bound


@pytest.mark.parametrize("feedback", [False, True],
                         ids=["stateless", "error_feedback"])
def test_zero_with_a_bf16_dcn_hop_stays_within_the_wire_s_bound(
        monkeypatch, feedback):
    """Only the DCN hop of the gradient's reduce-scatter and of the
    update's all-gather is in bfloat16 (8 bits of mantissa): every
    parameter stays within 2 x 2**-8 of the largest movement a step
    made.  ``error_feedback`` composes with the plain ZeRO step: the
    residual rides the optimizer state and is not zero."""
    start, want_params, want_loss = _replicated_two_steps("sgd")
    state, loss = _zero_two_steps(
        "sgd", monkeypatch, True,
        dcn_compression=DcnCompression("bfloat16", error_feedback=feedback))
    moved = _tree_max_diff(want_params, start)
    diff = _tree_max_diff(state.params, want_params)
    assert ZERO_SGD_BOUND < diff <= 2 * 2.0 ** -8 * moved
    np.testing.assert_allclose(loss, want_loss, rtol=1e-3)
    residual = state.opt_state.residual
    assert (residual is not None) == feedback
    if feedback:
        assert max(float(jnp.abs(r).max()) for r in residual) > 0.0


# -- the multi-axis step --------------------------------------------------------


# Under tp > 1 the multi-axis step does NOT compute the gradient (found by
# this test, ISSUE 30; ROADMAP D18): it differentiates inside a
# ``shard_map(check_vma=False)``, where the transpose of the row-parallel
# ``psum`` is a ``psum``, so every tp-sharded leaf gets tp x its gradient
# and every leaf replicated over tp (layer norms, embeddings) gets one
# rank's partial sum.  The forward is right and dp x sp is exact; the tp
# case stays here, strict, so that the repair has to take the mark off.
_TP_GRADIENT = pytest.mark.xfail(
    strict=True, reason="ROADMAP D18: psum transposed to psum under "
    "check_vma=False; tp-sharded gradients come out tp times too large")


@pytest.mark.parametrize("dp,sp,tp,make_opt,atol", [
    pytest.param(4, 2, 1, *OPTIMIZERS[0].values, id="dp4_sp2-sgd"),
    pytest.param(4, 2, 1, *OPTIMIZERS[1].values, id="dp4_sp2-adamw"),
    pytest.param(2, 2, 2, *OPTIMIZERS[0].values, id="dp2_sp2_tp2-sgd",
                 marks=_TP_GRADIENT),
])
def test_sharded_step_over_dp_tp_sp_equals_one_device(
        dp, sp, tp, make_opt, atol):
    """The multi-axis step against the same model on a 1 x 1 x 1 mesh of
    one device: ``jax.value_and_grad`` and the optimizer by hand over the
    whole batch, from the same (global) parameters."""
    model = sh.MultiAxisTransformer(
        vocab=64, d_model=32, num_heads=4, num_layers=2, seq_len=16,
        dtype=jnp.float32)
    mesh = sh.multi_axis_mesh(dp=dp, sp=sp, tp=tp, devices=jax.devices()[:8])
    optimizer = make_opt()
    variables, pspecs = sh.init_sharded(model, mesh, jax.random.PRNGKey(0))
    opt_state, ospecs = sh.init_opt_sharded(optimizer, variables, mesh, pspecs)
    tok = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, 64)
    tgt = jax.random.randint(jax.random.PRNGKey(2), (4, 16), 0, 64)

    def whole(tree):
        """The mesh's global parameters as one device reads them: each tp
        shard of the fused qkv kernel is its own (q, k, v) of its own
        heads, so the columns (tp, 3, heads/tp, head_dim) become
        (3, tp, heads/tp, head_dim); every other leaf is the same array
        (copied: the step donates the buffers it is given)."""
        def leaf(path, x):
            x = np.array(x, copy=True)
            if "qkv" in jax.tree_util.keystr(path):
                d = x.shape[0]
                x = x.reshape(d, tp, 3, -1).transpose(0, 2, 1, 3).reshape(d, -1)
            return x

        return jax.tree_util.tree_map_with_path(leaf, tree)

    one = sh.multi_axis_mesh(dp=1, sp=1, tp=1, devices=jax.devices()[:1])
    want = jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, NamedSharding(one, s)),
        whole(variables), pspecs)
    want_opt = optimizer.init(want)

    def loss_of(p):
        return optax.softmax_cross_entropy_with_integer_labels(
            model.apply(p, tok).astype(jnp.float32), tgt).mean()

    @jax.jit
    def reference(p, o):
        loss, grads = jax.shard_map(
            jax.value_and_grad(loss_of), mesh=one, in_specs=(pspecs,),
            out_specs=(P(), pspecs), check_vma=False)(p)
        updates, o = optimizer.update(grads, o, p)
        return optax.apply_updates(p, updates), o, loss

    step = sh.make_sharded_train_step(model, optimizer, mesh, pspecs, ospecs)
    for _ in range(2):
        variables, opt_state, loss = step(variables, opt_state, tok, tgt)
        want, want_opt, want_loss = reference(want, want_opt)
        np.testing.assert_allclose(float(loss), float(want_loss),
                                   rtol=LOSS_RTOL)
    assert _tree_max_diff(whole(variables), want) <= atol


# -- what the step returns and what it takes ------------------------------------


def test_loss_is_the_mean_over_the_axis_and_replicated():
    model, x, y = _mlp()
    state, step = _replicated(model, optax.sgd(0.1), hvd.world_mesh(), x)
    params = jax.device_get(state.params)
    own = [float(training.softmax_cross_entropy(
        model.apply({"params": params}, x[r:r + 1]), y[r:r + 1]))
        for r in range(8)]
    assert max(own) - min(own) > 1e-3
    _, loss = step(state, x, y)
    np.testing.assert_allclose(float(loss), np.mean(own), rtol=LOSS_RTOL)
    assert loss.sharding.is_fully_replicated
    shards = [np.asarray(s.data) for s in loss.addressable_shards]
    assert len(shards) == 8 and all(s == shards[0] for s in shards)


@pytest.mark.parametrize("builder", ["replicated", "zero"])
def test_state_step_counts(builder):
    model, x, y = _mlp()
    if builder == "zero":
        state, step, _ = training.zero_train_setup(
            model, optax.sgd(0.1), jax.random.PRNGKey(0), x[:1])
    else:
        state, step = _replicated(model, optax.sgd(0.1), hvd.world_mesh(), x)
    assert int(state.step) == 0
    for n in (1, 2):
        state, _ = step(state, x, y)
        assert int(state.step) == n and state.step.dtype == jnp.int32


def test_donated_state_is_gone_after_the_call():
    model, x, y = _mlp()
    state, step = _replicated(model, optax.adamw(1e-2), hvd.world_mesh(), x)
    new_state, _ = step(state, x, y)
    assert all(leaf.is_deleted()
               for leaf in jax.tree_util.tree_leaves(state))
    assert not any(leaf.is_deleted()
                   for leaf in jax.tree_util.tree_leaves(new_state))


def _builders():
    """Each step builder with the least it takes to reach its body."""
    mlp, sgd = Mlp(), optax.sgd(0.1)
    return {
        "data_parallel_train_step": (
            training.data_parallel_train_step, (mlp, sgd)),
        "zero_train_setup": (
            training.zero_train_setup,
            (mlp, sgd, jax.random.PRNGKey(0), jnp.zeros((1, 8)))),
        "make_sharded_train_step": (
            sh.make_sharded_train_step, (None, sgd, None, None, None)),
    }


@pytest.mark.parametrize("builder", [
    "data_parallel_train_step", "zero_train_setup", "make_sharded_train_step"])
def test_the_staged_backward_s_options_are_gone(builder):
    """One way to build each step: ``overlap=``, ``segmenter=`` and
    ``bucket_bytes=`` (removed in PR 30, not deprecated) are a TypeError."""
    fn, args = _builders()[builder]
    gone = {"overlap": True, "segmenter": lambda *a: [], "bucket_bytes": 4096}
    assert not set(gone) & set(inspect.signature(fn).parameters)
    for name, value in gone.items():
        with pytest.raises(TypeError, match=name):
            fn(*args, **{name: value})


# -- asynchronous all-reduces: what the step asks of the compiler (ISSUE 25) --


def _fake_mesh(platforms, axis=WORLD_AXIS):
    """What ``exchange_compile_options`` reads of a mesh (its shape and
    its devices' platforms), for backends this suite cannot attach; the
    described v5e is in tests/test_chip_compile.py."""
    devices = np.empty(len(platforms), dtype=object)
    devices[:] = [types.SimpleNamespace(platform=p) for p in platforms]
    return types.SimpleNamespace(shape={axis: len(platforms)}, devices=devices)


def _tiny_lm(n_devices):
    from jax.sharding import Mesh

    hvd.init()
    mesh = Mesh(np.array(jax.devices()[:n_devices]), (WORLD_AXIS,))
    model, optimizer = Transformer(gpt_tiny()), optax.adamw(1e-3)
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (n_devices, 32), 0, gpt_tiny().vocab_size)

    def state():
        return training.replicate_state(training.create_train_state(
            model, optimizer, jax.random.PRNGKey(0), tokens[:1]), mesh)

    return mesh, model, optimizer, tokens, state


class TestExchangeCompileOptions:
    def test_cpu_mesh_of_four_gets_none_and_the_step_is_the_plain_jit(self):
        mesh, model, optimizer, tokens, state = _tiny_lm(4)
        assert spmd_ops.exchange_compile_options(mesh) == {}
        step = training.data_parallel_train_step(model, optimizer, mesh=mesh)
        plain = jax.jit(step.__wrapped__, donate_argnums=(0,))
        got_state, got_loss = step(state(), tokens, tokens)
        want_state, want_loss = plain(state(), tokens, tokens)
        assert np.asarray(got_loss) == np.asarray(want_loss)
        assert _tree_bit_equal(got_state.params, want_state.params)
        # nothing asynchronous on this backend, and the counter says so
        counts = compiled_collective_counts(
            step.lower(state(), tokens, tokens).compile().as_text())
        assert counts["async_pairs"] == 0 and counts["sync_all_reduces"] >= 1

    @pytest.mark.parametrize("mesh", [
        pytest.param(lambda: _tiny_lm(1)[0], id="one_cpu_device"),
        pytest.param(lambda: _fake_mesh(["tpu"]), id="one_tpu"),
        pytest.param(lambda: _fake_mesh(["gpu"] * 4), id="four_gpus"),
        pytest.param(lambda: _fake_mesh(["tpu", "tpu", "cpu", "tpu"]),
                     id="mixed"),
    ])
    def test_none_without_an_exchange_or_off_the_tpu(self, mesh):
        assert spmd_ops.exchange_compile_options(mesh()) == {}

    def test_tpu_axis_of_four_gets_the_asynchronous_set(self):
        mesh = _fake_mesh(["tpu"] * 4)
        options = spmd_ops.exchange_compile_options(mesh)
        assert options["xla_enable_async_all_reduce"] is True
        assert options["xla_tpu_enable_async_collective_fusion"] is True
        assert options[
            "xla_tpu_enable_async_collective_fusion_fuse_all_reduce"] is True
        # a fresh dict each call: a caller may add to it
        options["x"] = 1
        assert "x" not in spmd_ops.exchange_compile_options(mesh)
        # the axis that is reduced over decides, not the mesh's size
        two_axes = types.SimpleNamespace(
            shape={"data": 4, "model": 1}, devices=mesh.devices)
        assert (spmd_ops.exchange_compile_options(two_axes, "data")
                == spmd_ops.exchange_compile_options(mesh))
        assert spmd_ops.exchange_compile_options(two_axes, "model") == {}


_COMPILED_TEXT = """HloModule jit__step, is_scheduled=true
%region_1.1 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.1 = f32[] add(%a, %b)
}
%fused_computation.1 (param_0.1: f32[8]) -> (f32[8], u32[]) {
  %param_0.1 = f32[8]{0} parameter(0)
  %all-reduce.7 = f32[8]{0} all-reduce(%param_0.1), channel_id=1, replica_groups={{0,1,2,3}}, to_apply=%region_1.1
  ROOT %custom-call.1 = (f32[8]{0}, u32[]) custom-call(%all-reduce.7), custom_call_target="AsyncCollectiveStart"
}
%fused_computation.2 (param_0.2: f32[8], param_1.2: f32[8,8]) -> f32[8,8] {
  %param_0.2 = f32[8]{0} parameter(0)
  %param_1.2 = f32[8,8]{1,0} parameter(1)
  %all-reduce.8 = f32[8]{0} all-reduce(%param_0.2), channel_id=1, replica_groups={{0,1,2,3}}, to_apply=%region_1.1
  ROOT %convolution.1 = f32[8,8]{1,0} convolution(%param_1.2, %param_1.2), dim_labels=bf_io->bf
}
%fused_computation.3 (param_0.3: f32[8]) -> f32[8] {
  %param_0.3 = f32[8]{0} parameter(0)
  %all-reduce.9 = f32[8]{0} all-reduce(%param_0.3), channel_id=1, replica_groups={{0,1,2,3}}, to_apply=%region_1.1
  ROOT %custom-call.2 = f32[8]{0} custom-call(%all-reduce.9), custom_call_target="AsyncCollectiveDone"
}
ENTRY %main (p0: f32[8], p1: f32[8,8], p2: f32[]) -> (f32[8], f32[8,8], f32[]) {
  %p0 = f32[8]{0} parameter(0)
  %p1 = f32[8,8]{1,0} parameter(1)
  %p2 = f32[] parameter(2)
  %async-collective-start = (f32[8]{0}, u32[]) fusion(%p0), kind=kCustom, calls=%fused_computation.1
  %fusion.5 = f32[8,8]{1,0} fusion(%p0, %p1), kind=kOutput, calls=%fused_computation.2
  %async-collective-done = f32[8]{0} fusion(%p0), kind=kCustom, calls=%fused_computation.3
  %psum.3 = f32[] all-reduce(%p2), channel_id=2, replica_groups={{0,1,2,3}}, to_apply=%region_1.1
  SYNC_LINE
  ROOT %tuple.1 = (f32[8]{0}, f32[8,8]{1,0}, f32[]) tuple(%async-collective-done, %fusion.5, %psum.3)
}
"""


@pytest.mark.parametrize("extra,want", [
    pytest.param("", {"async_pairs": 1, "sync_all_reduces": 1},
                 id="one_fused_pair_and_the_scalar"),
    pytest.param(
        "%all-reduce.3 = f32[8]{0} all-reduce(%p0), channel_id=3, "
        "replica_groups={{0,1,2,3}}, to_apply=%region_1.1, "
        'frontend_attributes={async_collective_name="all-reduce-start.1"}',
        {"async_pairs": 1, "sync_all_reduces": 2}, id="one_turned_back"),
    pytest.param(
        "%all-reduce-start.1 = f32[8]{0} all-reduce-start(%p0), "
        "channel_id=3, replica_groups={{0,1,2,3}}, to_apply=%region_1.1\n"
        "  %all-reduce-done.1 = f32[8]{0} all-reduce-done("
        "%all-reduce-start.1)",
        {"async_pairs": 2, "sync_all_reduces": 1}, id="the_generic_pair"),
])
def test_compiled_collective_counts_reads_the_schedule(extra, want):
    """The all-reduce repeated inside the fusions of an asynchronous
    collective is not a synchronous one; one in ENTRY is, whatever
    name it still carries."""
    text = _COMPILED_TEXT.replace("SYNC_LINE", extra)
    assert compiled_collective_counts(text) == want
