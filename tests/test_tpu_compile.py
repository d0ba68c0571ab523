"""Ahead-of-time compiles of the CNN main paths for a TPU v5e, no chip needed.

Each test compiles — never runs — the Pallas kernels or the trainer's node
step at Table-2 case7 widths (10 conv layers of 12 3x3 filters on
32x32x3 images, 7 FC layers of 2000 neurons, 10 classes), some at
case4's (8 conv x 8 filters, 5 FC x 1500), and the depthwise kernels and
node step of ConvNeXt-T at its published widths (16 images of 224 px a
node), for one chip of a described ``v5e:2x2`` topology.  The TPU
compiler refuses here what interpret mode cannot see: a block the
lowering does not accept, a grid cell that overflows VMEM.  A pass is a
compile, not a chip run.  Each kernel carries its stable ``name`` into
the lowered program, where the profiler's trace finds it.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and a worker that is not
given this file must still collect the same tests.  Compiled kernels and
the Pallas impl are forced inside the tests, not through a program option.
"""
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import MAX_TILE, dense, ops
from repro.models.cnn import (_conv_shapes, cnn_accuracy, cnn_loss, init_cnn,
                              make_case)
from repro.models.convnext import CONVNEXT_T, convnext_loss, init_convnext

CASE = make_case("case7")
CASE4 = make_case("case4")
NODES, BATCH = 4, 64          # m nodes x the benchmarks' per-node batch


def _case_shapes(cfg):
    shapes, final = _conv_shapes(cfg)
    convs = sorted({(size, cin, cout) for cin, cout, size, _ in shapes},
                   reverse=True)
    pools = sorted({(size, cout) for _, cout, size, pooled in shapes
                    if pooled}, reverse=True)
    dims = ([final * final * cfg.filters]
            + [cfg.fc_neurons] * (cfg.fc_layers - 1) + [cfg.num_classes])
    denses = sorted(set(zip(dims[:-1], dims[1:])))
    return convs, pools, denses


CONVS, POOLS, DENSES = _case_shapes(CASE)
DENSES4 = [d for d in _case_shapes(CASE4)[2] if d not in DENSES]


@pytest.fixture(scope="module")
def topo():
    mp = pytest.MonkeyPatch()
    mp.setenv("TPU_LOG_DIR", "disabled")     # no compiler logs on disk
    from jax.experimental import topologies
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:                   # noqa: BLE001 — any refusal
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        mp.undo()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Pallas impl, compiled (not interpreted) kernels, no cache writes:
    a compile for a described chip cannot be read back from the
    persistent cache, so the cache stays off around these tests."""
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "pallas")
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    ops.clear_fallback_log()
    yield
    jax.config.update("jax_enable_compilation_cache", cache_on)
    ops.clear_fallback_log()


def _compile(fn, *args, names=()):
    lowered = jax.jit(fn).lower(*args)
    text = lowered.as_text()
    for name in names:
        assert f'kernel_name = "{name}"' in text, f"no kernel named {name}"
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text(), "no Pallas kernel"
    assert ops.fallback_events() == {}
    return compiled


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _grad(f):
    return jax.grad(lambda *a: jnp.sum(f(*a)), argnums=tuple(range(3)))


@pytest.mark.parametrize("direction", ["forward", "backward"])
@pytest.mark.parametrize("size,cin,cout", CONVS)
def test_conv2d_compiles(one_chip, compiled_kernels, size, cin, cout,
                         direction):
    def f(x, w, b):
        return ops.conv2d(x, w, b, activation="relu", impl="pallas")

    k = CASE.filter_size
    args = (_spec(one_chip, (BATCH, size, size, cin)),
            _spec(one_chip, (k, k, cin, cout)), _spec(one_chip, (cout,)))
    if direction == "forward":
        _compile(f, *args, names=("conv_fwd",))
    else:
        _compile(_grad(f), *args, names=("conv_fwd", "conv_dx", "conv_dw"))


@pytest.mark.parametrize("direction", ["forward", "backward"])
@pytest.mark.parametrize("size,channels", POOLS)
def test_max_pool2d_compiles(one_chip, compiled_kernels, size, channels,
                             direction):
    def f(x):
        return ops.max_pool2d(x, impl="pallas")

    g = jax.grad(lambda x: jnp.sum(f(x) ** 2))
    x = _spec(one_chip, (BATCH, size, size, channels))
    if direction == "forward":
        _compile(f, x, names=("pool_fwd",))
    else:
        _compile(g, x, names=("pool_fwd", "pool_bwd"))


@pytest.mark.parametrize("direction", ["forward", "backward"])
@pytest.mark.parametrize("d_in,d_out", DENSES + DENSES4)
def test_dense_compiles(one_chip, compiled_kernels, d_in, d_out,
                        direction):
    def f(x, w, b):
        return ops.dense(x, w, b, activation="relu", impl="pallas")

    args = (_spec(one_chip, (BATCH, d_in)), _spec(one_chip, (d_in, d_out)),
            _spec(one_chip, (d_out,)))
    _compile(f if direction == "forward" else _grad(f), *args,
             names=("dense_matmul",))


@pytest.mark.parametrize("direction", ["forward", "backward"])
@pytest.mark.parametrize("rows,d_in,d_out", [(256, 1400, 1400),
                                             (1400, 256, 768)])
def test_dense_masked_k_compiles(one_chip, compiled_kernels, rows, d_in,
                                 d_out, direction):
    """Contractions too deep for one whole-K block and no lane multiple
    (Din = 1400 in the forward and dx, 1400 rows in dw) take 512-deep K
    blocks whose ragged tail the kernel masks; the TPU compiler must
    accept that select on both the lane and the sublane axis."""
    def f(x, w, b):
        return ops.dense(x, w, b, activation="relu", impl="pallas",
                         block=512)

    # the deeper of the two contractions, against the other's row tile
    deep, tm = max((d_in, rows), (rows, d_in))
    tk = dense._k_tile(deep, 2 * (tm + 512) * 4)
    assert tk == MAX_TILE and deep % tk, "not the masked path"
    args = (_spec(one_chip, (rows, d_in)), _spec(one_chip, (d_in, d_out)),
            _spec(one_chip, (d_out,)))
    _compile(f if direction == "forward" else _grad(f), *args,
             names=("dense_matmul",))


def _node_programs(one_chip, cfg):
    """The fused SGWU round's core — m nodes' value_and_grad of Eq. 16
    in one vmapped program — and the vmapped Q eval of the m nodes on
    256 held-out images, each with the arguments it is compiled for."""
    params = jax.eval_shape(lambda: init_cnn(jax.random.PRNGKey(0), cfg))
    stacked = jax.tree_util.tree_map(
        lambda a: _spec(one_chip, (NODES,) + a.shape, a.dtype), params)
    size, chans = cfg.image_size, cfg.in_channels
    batch = {"images": _spec(one_chip, (NODES, BATCH, size, size, chans)),
             "labels": _spec(one_chip, (NODES, BATCH), jnp.int32)}
    held_out = {"images": _spec(one_chip, (256, size, size, chans)),
                "labels": _spec(one_chip, (256,), jnp.int32)}
    step = jax.vmap(jax.value_and_grad(lambda p, b: cnn_loss(p, b, cfg)))
    evaluate = jax.vmap(lambda p, b: cnn_accuracy(p, b, cfg),
                        in_axes=(0, None))
    return {"step": (step, (stacked, batch)),
            "eval": (evaluate, (stacked, held_out))}


def test_vmapped_node_step_compiles(one_chip, compiled_kernels):
    """The fused SGWU round's core: m nodes' value_and_grad of Eq. 16 in
    one vmapped program, with every layer a compiled Pallas kernel."""
    step, args = _node_programs(one_chip, CASE)["step"]
    compiled = _compile(step, *args)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 16 * 10**9       # one v5e's HBM


# An HLO instruction's result: dtype[dims]{minor_to_major...} op(
_HLO_OP = re.compile(r"= \w+\[([\d,]*)\]\{([\d,]*)[^}]*\} "
                     r"(pad|copy|transpose|slice)\(")


def _weight_staging(text: str, floor: int) -> list[str]:
    """The instructions of a compiled program that stage a copy of a
    whole FC weight (``floor`` elements or more): a pad, a transpose or a
    slice of it, or a copy that swaps its two matrix axes (the minor
    axis of the result is its rows).  A copy that only moves the node
    axis is the TPU's own default layout of a stacked weight whose rows
    are no multiple of 8 (case4's [4,1500,1500] is (1,0,2)-major,
    (4,128)-tiled) relaid for the kernel, and is not counted.
    """
    found = []
    for line in text.splitlines():
        m = _HLO_OP.search(line)
        if not m or not m.group(1):
            continue
        dims = [int(d) for d in m.group(1).split(",")]
        if len(dims) < 2 or math.prod(dims) < floor:
            continue
        order = [int(d) for d in m.group(2).split(",")]
        rows, cols = len(dims) - 2, len(dims) - 1
        if m.group(3) != "copy" or order.index(rows) < order.index(cols):
            found.append(line.strip()[:160])
    return found


@pytest.mark.parametrize("program", ["step", "eval"])
@pytest.mark.parametrize("cfg", [CASE, CASE4], ids=["case7", "case4"])
def test_fc_weights_are_read_in_place(one_chip, compiled_kernels, cfg,
                                      program):
    """The dense kernels read every FC weight where it lies: no pad to
    tile multiples, no materialized ``w.T``, no slice of a padded result
    the size of a weight in the m-node programs."""
    fn, args = _node_programs(one_chip, cfg)[program]
    text = _compile(fn, *args, names=("dense_matmul",)).as_text()
    staged = _weight_staging(text, NODES * cfg.fc_neurons ** 2)
    assert staged == [], "\n".join(staged)


def test_case_shapes_cover_case7():
    # 32 -> 16 -> 8 -> 4 px: three pooled conv widths, then 4 px convs
    assert [c[0] for c in CONVS] == [32, 16, 8, 4]
    assert DENSES == [(192, 2000), (2000, 10), (2000, 2000)]


# ConvNeXt-T's depthwise stages: (map size, channels), 16 images a node
DWCONVS = [(56, 96), (28, 192), (14, 384), (7, 768)]
CONVNEXT_BATCH = 16


@pytest.mark.parametrize("direction", ["forward", "backward"])
@pytest.mark.parametrize("size,channels", DWCONVS)
def test_depthwise_conv_compiles(one_chip, compiled_kernels, size, channels,
                                 direction):
    """Each cell holds one padded 7x7 plane of its image in VMEM: the
    widest, stage 1's 62x62x96, must fit the default scoped limit."""
    def f(x, w, b):
        return ops.depthwise_conv2d(x, w, b, impl="pallas")

    args = (_spec(one_chip, (CONVNEXT_BATCH, size, size, channels)),
            _spec(one_chip, (7, 7, channels)), _spec(one_chip, (channels,)))
    if direction == "forward":
        _compile(f, *args, names=("dwconv_fwd",))
    else:        # the gradient of a sum needs no forward value
        _compile(_grad(f), *args, names=("dwconv_dx", "dwconv_dw"))


def test_vmapped_convnext_node_step_compiles(one_chip, compiled_kernels):
    """The fused SGWU round's core for ConvNeXt-T at 224 px: m nodes'
    value_and_grad in one vmapped program, the depthwise convs, the
    patchify convs and the pointwise MLPs all compiled Pallas kernels,
    and the activations of 4 x 16 images within one v5e's HBM."""
    params = jax.eval_shape(lambda: init_convnext(jax.random.PRNGKey(0)))
    stacked = jax.tree_util.tree_map(
        lambda a: _spec(one_chip, (NODES,) + a.shape, a.dtype), params)
    size = CONVNEXT_T.image_size
    batch = {"images": _spec(one_chip, (NODES, CONVNEXT_BATCH, size, size,
                                        CONVNEXT_T.in_channels)),
             "labels": _spec(one_chip, (NODES, CONVNEXT_BATCH), jnp.int32)}
    step = jax.vmap(jax.value_and_grad(lambda p, b: convnext_loss(p, b)))
    compiled = _compile(step, stacked, batch,
                        names=("dwconv_fwd", "dwconv_dx", "dwconv_dw",
                               "dense_matmul"))
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16 * 10**9
