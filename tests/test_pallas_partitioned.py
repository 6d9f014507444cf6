"""The BERT kernels inside a program GSPMD partitions (ISSUE 45): under
``pallas_common.auto_partitioned(mesh, batch=(axes, size))`` on a mesh
of several devices the packed self-attention and the dropout kernels
run once a shard, on the shard's own rows (``pallas_common.per_shard``:
a ``jax.shard_map`` over the batch axes), and give what the one-device
kernel gives on the whole array. The layer-norm kernel, which a shard
each lost to XLA's fusions on the chip (PERF.md section 6, PR 45), and
every decoder kernel keep their compositions; the two Dense epilogues
are compositions everywhere (PR 48).
Four host devices, kernels interpreted; what Mosaic and the v5e:2x2
compiler say of the same calls is ``tests/test_chip_compile_*.py``'s, the
in-kernel PRNG's masks ``chip_smoke.py --chips 4``'s.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from mxnet_tpu import telemetry
from mxnet_tpu.ops import (get_op, pallas_attention, pallas_common,
                           pallas_dropout, pallas_norm)
from mxnet_tpu.ops.pallas_common import auto_partitioned

SHARDS = 4
BATCH, OTHER, C = 8, 16, 128      # the batch never equals the length
LAYOUTS = {"LNC": 1, "BTC": 0}    # layout -> the batch's dimension


def _mesh(n=SHARDS, names=("dp",), shape=None):
    devs = np.array(jax.devices()[:n])
    return Mesh(devs.reshape(shape or (n,)), names)


def _shape(layout, c=C, batch=BATCH):
    return (OTHER, batch, c) if layout == "LNC" else (batch, OTHER, c)


def _spec(dim):
    return P(*([None] * dim + ["dp"]))


def _randn(shape, dtype, seed=0):
    x = np.random.RandomState(seed).standard_normal(shape)
    return jnp.asarray(x, dtype)


def _sharded(fn, mesh, operands, dims, batch=BATCH, axes="dp"):
    """``fn`` jitted on the mesh, traced inside the scope, its operands
    placed split on ``dims`` (None: replicated)."""
    def scoped(*a):
        with auto_partitioned(mesh, batch=(axes, batch)):
            return fn(*a)
    placed = [jax.device_put(x, NamedSharding(
        mesh, P() if d is None else _spec(d)))
        for x, d in zip(operands, dims)]
    return jax.jit(scoped), placed


class _Counts:
    """``mx_pallas_partitioned_total`` over a block of code."""
    KERNELS = ("pallas_dropout", "pallas_selfatt_packed")

    def _read(self):
        return {(k, h): telemetry.counter(
            "mx_pallas_partitioned_total", kernel=k, how=h).get()
            for k in self.KERNELS for h in ("sharded", "composition")}

    def __enter__(self):
        self._was = telemetry.enabled()
        telemetry.enable(True)
        self._start = self._read()
        return self

    def __exit__(self, *exc):
        now = self._read()
        self.got = {k: n - self._start[k] for k, n in now.items()
                    if n != self._start[k]}
        telemetry.enable(self._was)


# ---------------------------------------------------------------------------
# attention: N is second, the seeds split with it
# ---------------------------------------------------------------------------
HEADS, D = 4, 32


def _attention(p, block_heads=None):
    def op(qkv, seeds):
        return pallas_attention.flash_selfatt(
            qkv, seeds, heads=HEADS, dropout=p, block_heads=block_heads)
    return op


@pytest.mark.parametrize("p", [0.0, 0.1], ids=["p0", "p0.1"])
@pytest.mark.parametrize("length", [OTHER, 13], ids=["L16", "L13"])
def test_attention_a_shard_is_the_kernel_on_the_whole_batch(length, p):
    """Bit for bit the one-device call with the same seeds, value and
    gradient: at p 0.1 that is every shard's backward regenerating its
    forward's mask, from its own slice of the seeds."""
    plan = pallas_attention.selfatt_plan(length, HEADS, BATCH, p,
                                         dtype=jnp.bfloat16, head_dim=D)
    qkv = _randn((length, BATCH, 3 * HEADS * D), jnp.bfloat16)
    seeds = jnp.arange(1, plan["n_blocks"] + 1, dtype=jnp.int32) * 7919
    cot = _randn((length, BATCH, HEADS * D), jnp.bfloat16, 3)
    op = _attention(p, plan["bbh"])

    def step(qkv, seeds, cot):
        f = lambda q: jnp.sum((op(q, seeds) * cot).astype(jnp.float32))
        return op(qkv, seeds), jax.grad(f)(qkv)

    want, want_grad = jax.jit(step)(qkv, seeds, cot)
    with _Counts() as counts:
        fn, placed = _sharded(step, _mesh(), (qkv, seeds, cot), (1, 0, 1))
        got, got_grad = fn(*placed)
    assert counts.got == {("pallas_selfatt_packed", "sharded"): 2}
    assert got.sharding.spec == _spec(1)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    np.testing.assert_array_equal(np.asarray(got_grad, np.float32),
                                  np.asarray(want_grad, np.float32))


def test_no_two_shards_of_the_op_draw_one_attention_mask():
    """Every sample the same: what differs between two shards' outputs
    is their masks. The op draws one seed a (sample, head block) of the
    WHOLE batch and plans the block for a shard's share of it."""
    op = get_op("_contrib_sdp_selfatt").impl
    one = _randn((OTHER, 1, 3 * HEADS * D), jnp.bfloat16)
    qkv = jnp.tile(one, (1, BATCH, 1))

    def fwd(qkv):
        return op(jax.random.key(3), qkv, heads=HEADS, dropout=0.1,
                  _train=True)

    with _Counts() as counts:
        fn, placed = _sharded(fwd, _mesh(), (qkv,), (1,))
        out = np.asarray(fn(*placed), np.float32)
    assert counts.got == {("pallas_selfatt_packed", "sharded"): 1}
    per = BATCH // SHARDS
    shards = [out[:, i * per:(i + 1) * per] for i in range(SHARDS)]
    for i in range(SHARDS):
        for j in range(i + 1, SHARDS):
            assert not np.array_equal(shards[i], shards[j]), (i, j)


def test_the_plan_is_a_shard_s_and_the_seeds_are_the_batch_s():
    whole = pallas_attention.selfatt_plan(OTHER, HEADS, BATCH, 0.1,
                                          dtype=jnp.bfloat16, head_dim=D)
    seen = []
    real = pallas_attention._resolve_plan

    def spy(L, L_pad, heads, batch, *a):
        seen.append(batch)
        return real(L, L_pad, heads, batch, *a)

    pallas_attention._resolve_plan = spy
    try:
        with auto_partitioned(_mesh(), batch=("dp", BATCH)):
            plan = pallas_attention.selfatt_plan(
                OTHER, HEADS, BATCH, 0.1, dtype=jnp.bfloat16, head_dim=D)
    finally:
        pallas_attention._resolve_plan = real
    assert seen == [BATCH // SHARDS]
    assert plan == whole


# ---------------------------------------------------------------------------
# dropout: the kernel has no interpreter form (the TPU's PRNG), so a
# stand-in with the kernel's contract, a mask a row block from that
# block's seed, shows what the wrapper does with the seeds
# ---------------------------------------------------------------------------
@pytest.fixture
def hashed_dropout(monkeypatch):
    def call(M, C, bm, p, dtype_name, backward, interpret):
        def run(seeds, x):
            rows = jnp.arange(M, dtype=jnp.uint32)
            z = (jnp.repeat(seeds.astype(jnp.uint32), bm)[:, None]
                 * jnp.uint32(0x9E3779B9)
                 + (rows % bm)[:, None] * jnp.uint32(C)
                 + jnp.arange(C, dtype=jnp.uint32)[None])
            z = (z ^ (z >> 16)) * jnp.uint32(0x85EBCA6B)
            z = (z ^ (z >> 13)) * jnp.uint32(0xC2B2AE35)
            keep = (z ^ (z >> 16)) >= jnp.uint32(int(p * 2 ** 32))
            return jnp.where(keep, x / (1.0 - p), 0).astype(x.dtype)
        return run

    monkeypatch.setattr(pallas_dropout, "_drop_call", call)
    monkeypatch.setattr(pallas_dropout, "_interpret", lambda: False)
    pallas_dropout._make_op.cache_clear()
    yield
    pallas_dropout._make_op.cache_clear()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_dropout_a_shard_draws_its_own_masks_and_its_backward_finds_them(
        hashed_dropout, layout, dtype):
    """``(L, N, C)`` with ``N`` split and ``(B, T, C)`` with ``B``
    split: the row-wise kernel finds the batch by its size."""
    shape, dim = _shape(layout, batch=64), LAYOUTS[layout]
    x = jnp.ones(shape, dtype)

    def step(x):
        y, vjp = jax.vjp(
            lambda a: pallas_dropout.pallas_dropout(jax.random.key(5), a,
                                                    0.5), x)
        return y, vjp(jnp.ones_like(y))[0]

    with _Counts() as counts:
        fn, placed = _sharded(step, _mesh(), (x,), (dim,), batch=64)
        y, dx = fn(*placed)
    assert counts.got == {("pallas_dropout", "sharded"): 1}
    assert y.sharding.spec == _spec(dim)
    y, dx = np.asarray(y, np.float32), np.asarray(dx, np.float32)
    np.testing.assert_array_equal(y, dx)       # the forward's mask
    assert 0.4 < (y != 0).mean() < 0.6
    per = 64 // SHARDS
    shards = [np.take(y, range(i * per, (i + 1) * per), axis=dim)
              for i in range(SHARDS)]
    for i in range(SHARDS):
        for j in range(i + 1, SHARDS):
            assert not np.array_equal(shards[i], shards[j]), (i, j)


def test_dropout_s_seeds_are_one_a_row_block_of_every_shard(hashed_dropout,
                                                           monkeypatch):
    """Hazards 2 and 3: the availability test and the row block see a
    shard's rows, and the seeds are drawn for every block of every
    shard."""
    picked = []
    pick = pallas_dropout._pick_rows

    def spy_rows(M, *a):
        picked.append(M)
        return pick(M, *a)

    monkeypatch.setattr(pallas_dropout, "_pick_rows", spy_rows)
    drawn = []
    real = jax.random.randint

    def spy(key, shape, *a, **k):
        drawn.append(tuple(shape))
        return real(key, shape, *a, **k)

    monkeypatch.setattr(jax.random, "randint", spy)
    shape = _shape("LNC", batch=64)
    rows = OTHER * 64 // SHARDS
    bm = pallas_dropout._pick_rows(rows, C, 2)
    with auto_partitioned(_mesh(), batch=("dp", 64)):
        assert pallas_dropout.pallas_dropout_available(shape, jnp.bfloat16,
                                                       0.5)
        jax.make_jaxpr(lambda x: pallas_dropout.pallas_dropout(
            jax.random.key(0), x, 0.5))(jnp.ones(shape, jnp.bfloat16))
    assert drawn == [(SHARDS * (rows // bm),)]
    assert set(picked) == {rows}


# ---------------------------------------------------------------------------
# the traced and the compiled program
# ---------------------------------------------------------------------------
def _ops_of(jaxpr, found=None):
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        found.append(eqn.primitive.name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _ops_of(sub, found)
    return found


def _dropout(x):
    return pallas_dropout.pallas_dropout(jax.random.key(1), x, 0.5)


def _program_cases():
    plan = pallas_attention.selfatt_plan(OTHER, HEADS, BATCH, 0.0,
                                         dtype=jnp.bfloat16, head_dim=D)
    return {
        "dropout": (_dropout, (_randn(_shape("LNC"), jnp.bfloat16),), (1,)),
        "dropout-BTC": (_dropout, (_randn(_shape("BTC"), jnp.bfloat16),),
                        (0,)),
        "attention": (_attention(0.0, plan["bbh"]), (
            _randn((OTHER, BATCH, 3 * HEADS * D), jnp.bfloat16),
            jnp.zeros((plan["n_blocks"],), jnp.int32)), (1, 0)),
    }


@pytest.mark.parametrize("kernel", ["dropout", "dropout-BTC", "attention"])
def test_one_call_a_shard_and_nothing_gathered(hashed_dropout, kernel):
    """Forward: one ``shard_map`` holding the call (attention's
    ``pallas_call``; dropout's stand-in has none); forward + backward
    compiled for the four devices: no ``all-gather``, no ``all-to-all``
    (hazard 1: the flatten is a shard's own)."""
    op, args, dims = _program_cases()[kernel]
    mesh = _mesh()
    with auto_partitioned(mesh, batch=("dp", BATCH)):
        ops = _ops_of(jax.make_jaxpr(op)(*args).jaxpr)
    assert ops.count("shard_map") == 1
    assert ops.count("pallas_call") == (kernel == "attention")
    grad = jax.grad(lambda *a: jnp.sum(op(*a).astype(jnp.float32)))
    fn, placed = _sharded(grad, mesh, args, dims)
    text = fn.lower(*placed).compile().as_text()
    assert "all-gather" not in text and "all-to-all" not in text


@pytest.mark.parametrize("kernel", ["dropout", "dropout-BTC", "attention"])
def test_a_mesh_of_one_device_traces_the_program_it_always_did(
        hashed_dropout, kernel):
    op, args, _ = _program_cases()[kernel]
    grad = jax.value_and_grad(
        lambda *a: jnp.sum(op(*a).astype(jnp.float32)))
    want = str(jax.make_jaxpr(grad)(*args))
    with _Counts() as counts:
        with auto_partitioned(_mesh(1), batch=("dp", BATCH)):
            got = str(jax.make_jaxpr(grad)(*args))
    assert got == want and "shard_map" not in got
    assert counts.got == {}


# ---------------------------------------------------------------------------
# what a shard cannot split keeps the composition, and is counted
# ---------------------------------------------------------------------------
def _availability(shape):
    return {
        "pallas_dropout": pallas_dropout.pallas_dropout_available(
            shape, jnp.bfloat16, 0.5),
        "pallas_selfatt_packed": pallas_attention.selfatt_plan(
            shape[0], HEADS, shape[1], 0.0, dtype=jnp.bfloat16,
            head_dim=D) is not None,
    }


@pytest.mark.parametrize("why, mesh, batch, shape", [
    ("a batch of 6 on 4 shards", dict(), ("dp", 6), (OTHER, 6, C)),
    ("the batch is no dimension but the last", dict(), ("dp", C),
     (OTHER, BATCH, C)),
    ("two dimensions hold the batch", dict(), ("dp", OTHER),
     (OTHER, OTHER, C)),
    ("the scope was told no batch", dict(), None, (OTHER, BATCH, C)),
    ("a tensor-parallel axis splits the program too",
     dict(names=("dp", "tp"), shape=(2, 2)), ("dp", BATCH),
     (OTHER, BATCH, C)),
], ids=lambda v: v.replace(" ", "-") if isinstance(v, str) else None)
def test_what_cannot_run_a_shard_at_a_time_keeps_the_composition(
        hashed_dropout, why, mesh, batch, shape):
    assert all(_availability(shape).values())       # outside: the kernels
    with _Counts() as counts:
        with auto_partitioned(_mesh(**mesh), batch=batch):
            inside = _availability(shape)
            assert not pallas_common.kernels_allowed()
    # attention's batch is its second dimension, whatever else is as long
    want = dict.fromkeys(inside, False)
    want["pallas_selfatt_packed"] = why == "two dimensions hold the batch"
    assert inside == want
    assert counts.got == {(k, "composition"): 1
                          for k, taken in want.items() if not taken}


def test_a_composition_op_under_the_scope_is_the_sum_gspmd_partitions():
    """The registered op on a batch that does not divide: the XLA
    composition, no ``shard_map`` in the program."""
    op = get_op("_contrib_sdp_selfatt").impl
    qkv = _randn((OTHER, 6, 3 * HEADS * D), jnp.bfloat16)
    with auto_partitioned(_mesh(), batch=("dp", 6)):
        ops = _ops_of(jax.make_jaxpr(lambda q: op(
            jax.random.key(0), q, heads=HEADS, dropout=0.1,
            _train=True))(qkv).jaxpr)
    assert "shard_map" not in ops and "pallas_call" not in ops
    assert ops.count("dot_general") == 2


def test_a_kernel_called_past_its_availability_says_so():
    qkv = _randn((OTHER, 6, 3 * HEADS * D), jnp.bfloat16)
    with auto_partitioned(_mesh(), batch=("dp", 6)):
        with pytest.raises(ValueError, match="a shard at a time"):
            pallas_attention.flash_selfatt(
                qkv, jnp.zeros((6,), jnp.int32), heads=HEADS,
                block_heads=HEADS)


def test_the_decoder_kernels_still_stand_down():
    """A kernel with no rule asks ``kernels_allowed``: False on several
    devices whatever the scope was told, True on one and outside."""
    with auto_partitioned(_mesh(), batch=("dp", BATCH)):
        assert not pallas_common.kernels_allowed()
        with auto_partitioned(_mesh(1)):
            assert pallas_common.kernels_allowed()
        assert not pallas_common.kernels_allowed()
    assert pallas_common.kernels_allowed()


@pytest.mark.parametrize("op, n_args", [("LayerNorm", 3),
                                        ("_contrib_bias_gelu", 2),
                                        ("_contrib_bias_add_residual", 3)])
def test_the_kernels_that_lost_keep_their_compositions_on_a_mesh(op, n_args):
    """The layer norm has no per-shard rule and the two epilogues no
    kernel: a shard each all three lost to XLA's own fusions in the
    dp=4 BERT step (PERF.md section 6, PR 45), the epilogues on one
    chip too (PR 48). One device: the layer norm's kernel, as ever."""
    shape = _shape("LNC")
    args = [_randn(shape, jnp.bfloat16), _randn((C,), jnp.bfloat16, 1),
            _randn(shape if op != "LayerNorm" else (C,), jnp.bfloat16, 2)]
    impl = get_op(op).impl

    def ops():
        # a function of its own a trace: jax caches a function's trace
        # by its arguments' shapes, not by this scope
        return _ops_of(jax.make_jaxpr(lambda *a: impl(*a))(
            *args[:n_args]).jaxpr)

    with _Counts() as counts:
        with auto_partitioned(_mesh(), batch=("dp", BATCH)):
            assert not pallas_norm.pallas_ln_available(shape, jnp.bfloat16,
                                                       2)
            on_mesh = ops()
        with auto_partitioned(_mesh(1), batch=("dp", BATCH)):
            on_one = ops()
    assert "pallas_call" not in on_mesh and "shard_map" not in on_mesh
    assert on_one.count("pallas_call") == (op == "LayerNorm")
    assert "shard_map" not in on_one
    assert counts.got == {}


# ---------------------------------------------------------------------------
# the step that opens the scope
# ---------------------------------------------------------------------------
def test_a_sharded_bert_step_runs_its_kernels_a_shard():
    """``ShardedTrainStep`` on ``dp=4`` tells the scope its batch: every
    attention call of the traced step takes the kernel, nothing takes a
    composition for the mesh's sake, and the losses are the one-device
    step's."""
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.gluon.model_zoo.bert import BERTMLMLoss, BERTModel
    from mxnet_tpu.parallel import (MeshConfig, ShardedTrainStep,
                                    make_mesh)
    layers, seq, vocab = 2, 16, 64

    class Loss:
        def __init__(self):
            self.head = BERTMLMLoss(vocab_size=vocab, units=128,
                                    prefix="decoder_")
            self.head.initialize()

        def collect_params(self):
            return self.head.collect_params()

        def __call__(self, outputs, labels):
            seq_out = outputs[0] if isinstance(outputs, (list, tuple)) \
                else outputs
            return [self.head(seq_out, labels).mean()]

    def build(devices):
        mx.random.seed(7)
        net = BERTModel(num_layers=layers, units=128, hidden_size=256,
                        num_heads=4, max_length=seq, vocab_size=vocab,
                        dropout=0.0, use_pooler=False,
                        use_classifier=False, use_decoder=False)
        net.initialize()
        net(nd.zeros((2, seq), dtype="int32"),
            nd.zeros((2, seq), dtype="int32"))
        mesh = make_mesh(MeshConfig(dp=devices),
                         devices=jax.devices()[:devices])
        return ShardedTrainStep(net, Loss(), mesh, optimizer="lamb",
                                lr=1e-3, dtype="bfloat16", n_data_inputs=3)

    def losses(step):
        rng = np.random.RandomState(0)
        ids = nd.array(rng.randint(0, vocab, (BATCH, seq)), dtype="int32")
        types = nd.zeros((BATCH, seq), dtype="int32")
        return [float(jax.device_get(step.step(ids, types, ids)))
                for _ in range(2)]

    def paths():
        return {p: telemetry.counter("mx_attn_selfatt_path_total",
                                     path=p).get() for p in ("pallas", "xla")}

    step = build(SHARDS)
    start = paths()
    with _Counts() as counts:
        got = losses(step)
        taken = {p: n - start[p] for p, n in paths().items()}
    assert taken == {"pallas": layers, "xla": 0}
    # dropout 0, and the in-kernel PRNG has no interpreter form anyway
    assert counts.got == {("pallas_selfatt_packed", "sharded"): layers}
    np.testing.assert_allclose(got, losses(build(1)), rtol=2e-2)
