"""Ahead-of-time compiles of the CNN main path for a TPU v5e, no chip needed.

Each test compiles — never runs — the Pallas kernels or the trainer's node
step at Table-2 case7 widths (10 conv layers of 12 3x3 filters on
32x32x3 images, 7 FC layers of 2000 neurons, 10 classes) for one chip of
a described ``v5e:2x2`` topology.  The TPU compiler refuses here what
interpret mode cannot see: a block the lowering does not accept, a grid
cell that overflows VMEM.  A pass is a compile, not a chip run.  Each
kernel carries its stable ``name`` into the lowered program, where the
profiler's trace finds it.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and a worker that is not
given this file must still collect the same tests.  Compiled kernels and
the Pallas impl are forced inside the tests, not through a program option.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.models.cnn import _conv_shapes, cnn_loss, init_cnn, make_case

CASE = make_case("case7")
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
@pytest.mark.parametrize("d_in,d_out", DENSES)
def test_dense_compiles(one_chip, compiled_kernels, d_in, d_out,
                        direction):
    def f(x, w, b):
        return ops.dense(x, w, b, activation="relu", impl="pallas")

    args = (_spec(one_chip, (BATCH, d_in)), _spec(one_chip, (d_in, d_out)),
            _spec(one_chip, (d_out,)))
    _compile(f if direction == "forward" else _grad(f), *args,
             names=("dense_matmul",))


def test_vmapped_node_step_compiles(one_chip, compiled_kernels):
    """The fused SGWU round's core: m nodes' value_and_grad of Eq. 16 in
    one vmapped program, with every layer a compiled Pallas kernel."""
    params = jax.eval_shape(lambda: init_cnn(jax.random.PRNGKey(0), CASE))
    stacked = jax.tree_util.tree_map(
        lambda a: _spec(one_chip, (NODES,) + a.shape, a.dtype), params)
    size, chans = CASE.image_size, CASE.in_channels
    batch = {"images": _spec(one_chip, (NODES, BATCH, size, size, chans)),
             "labels": _spec(one_chip, (NODES, BATCH), jnp.int32)}
    step = jax.vmap(jax.value_and_grad(lambda p, b: cnn_loss(p, b, CASE)))
    compiled = _compile(step, stacked, batch)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 16 * 10**9       # one v5e's HBM


def test_case_shapes_cover_case7():
    # 32 -> 16 -> 8 -> 4 px: three pooled conv widths, then 4 px convs
    assert [c[0] for c in CONVS] == [32, 16, 8, 4]
    assert DENSES == [(192, 2000), (2000, 10), (2000, 2000)]
