"""Main-path kernels compiled for a described TPU v5e (``v5e:2x2``).

Nothing runs: each test lowers and compiles for a chip that is described,
not attached, and checks that the kernel is in the program
(``tpu_custom_call``) or, for the sharded cart and row steps, that the halo's
``collective-permute`` is, and that the collect's packer holds no
collective. What the chip's compiler refuses (unaligned
slices, too much VMEM) fails here at no chip time. A compile that passes
is not a chip run.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""

from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from mpi_and_open_mp_tpu.ops import bitlife

N = jax.ShapeDtypeStruct((), jnp.int32)


@pytest.fixture(scope="module")
def no_compile_cache():
    """Compiles for a described chip cannot be read back without one, so
    the persistent cache stays off around them."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def topo(no_compile_cache):
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh_2x2(topo):
    import numpy as np
    from jax.sharding import AxisType, Mesh

    return Mesh(np.array(topo.devices).reshape(2, 2), ("y", "x"),
                axis_types=(AxisType.Auto, AxisType.Auto))


@pytest.fixture(scope="module")
def mesh_4x1(topo):
    """The same four chips as one ring of row strips: the ``y`` axis
    alone, as ``apps/life.py --layout row --devices 4`` meshes them."""
    import numpy as np
    from jax.sharding import AxisType, Mesh

    return Mesh(np.array(topo.devices).reshape(4), ("y",),
                axis_types=(AxisType.Auto,))


def _kernel_text(fn, shape, sharding) -> str:
    x = jax.ShapeDtypeStruct(shape, jnp.uint8, sharding=sharding)
    return jax.jit(fn).lower(x, N).compile().as_text()


@pytest.mark.parametrize("fn, shape", [
    (lambda b, n: bitlife.life_run_vmem_bits(b, n, interpret=False),
     (500, 500)),
    (lambda b, n: bitlife.life_run_fused_bits(b, n), (8192, 8192)),
    (lambda b, n: bitlife.life_run_frame_bits(b, n), (10000, 10000)),
    (lambda b, n: bitlife.life_run_bitsliced_batch(
        b, n, use_kernel=True, interpret=False), (64, 256, 256)),
    (lambda b, n: bitlife.life_run_vmem_bits_batch(b, n, interpret=False),
     (8, 512, 512)),
], ids=["vmem_500", "fused_8192", "frame_10000", "bitsliced_64x256",
        "vmem_batch_8x512"])
def test_single_chip_kernel_compiles(one_chip, fn, shape):
    assert "tpu_custom_call" in _kernel_text(fn, shape, one_chip)


@pytest.mark.parametrize("shape", [(8192, 8192), (10000, 10000)],
                         ids=["8192", "10000"])
def test_cart_bitfused_step_compiles_on_2x2(mesh_2x2, monkeypatch, shape):
    """The cart layout's bitfused halo program as ``LifeSim`` builds it
    (``_build_bitfused_advance``), steered onto its TPU branch: the
    stepper's kernel and the halo's collective-permute are both there.
    8192² is an exact frame; 10000² lives in a 10240² frame whose 240
    mirror rows and columns the padded exchange (funnel-shifted wrap
    ghosts, mirror refresh) keeps."""
    from mpi_and_open_mp_tpu.models.life import LifeSim

    plan = bitlife.plan_sharded_bits(shape, 2, 2,
                                     y_sharded=True, x_sharded=True)
    assert plan is not None and plan.mode == "tiled"
    assert (plan.pad_y > 0) == (plan.pad_x > 0) == (shape[0] % 128 > 0)
    # LifeSim.__init__ would place a board on the (undescribable) mesh;
    # only the fields the builder reads are set.
    sim = object.__new__(LifeSim)
    sim.layout, sim.mesh, sim._plan = "cart", mesh_2x2, plan
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    advance = sim._build_bitfused_advance()
    board = jax.ShapeDtypeStruct(
        plan.frame, jnp.uint8, sharding=NamedSharding(mesh_2x2, P("y", "x")))
    text = advance.lower(board, N).compile().as_text()
    assert "tpu_custom_call" in text
    assert "collective-permute" in text


def test_row_bitfused_window_step_compiles_on_4x1(mesh_4x1, monkeypatch):
    """The row layout's bitfused program for ``p46gun_big.cfg``'s 500²
    board over four strips, as ``LifeSim`` builds it on the chip: a
    512² frame, 4-word strips in window mode (``life_fused_window``)
    with an exact 500-wide wrap, and the funnel-shifted wrap ghosts of
    the 12 mirror rows on the ring's ``collective-permute``."""
    from mpi_and_open_mp_tpu.models.life import LifeSim

    plan = bitlife.plan_sharded_bits((500, 500), 4, 1,
                                     y_sharded=True, x_sharded=False)
    assert plan is not None and plan.mode == "window"
    assert (plan.nx_exact, plan.h, plan.pad_y) == (500, 3, 12)
    sim = object.__new__(LifeSim)
    sim.layout, sim.mesh, sim._plan = "row", mesh_4x1, plan
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    advance = sim._build_bitfused_advance()
    board = jax.ShapeDtypeStruct(
        plan.frame, jnp.uint8, sharding=NamedSharding(mesh_4x1, P("y", None)))
    text = advance.lower(board, N).compile().as_text()
    assert "tpu_custom_call" in text
    assert "collective-permute" in text


def test_rdma_edge_pair_compiles_on_2x2(mesh_2x2):
    """``haloplan._rdma_edge_pair`` over the y ring of the 2x2 mesh (the
    cart RDMA rung's phase 1): guards its ``pltpu.CompilerParams``."""
    from mpi_and_open_mp_tpu.parallel import haloplan

    spec = P("y", "x")
    pair = jax.shard_map(
        lambda f, b: haloplan._rdma_edge_pair(f, b, "y", 2, collective_id=13),
        mesh=mesh_2x2, in_specs=(spec, spec), out_specs=(spec, spec),
        check_vma=False)
    edge = jax.ShapeDtypeStruct(
        (2 * 8, 2 * 256), jnp.uint32,
        sharding=NamedSharding(mesh_2x2, spec))
    text = jax.jit(pair).lower(edge, edge).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("where", ["one_chip", "mesh_2x2"])
def test_collect_pack_compiles_without_collectives(request, where):
    """``LifeSim.collect()``'s packer at 8192² as ``_build_pack`` builds
    it, on one chip and sharded ``P("y", "x")`` over the 2x2 mesh: each
    shard packs its own cells, so the program holds no collective."""
    from mpi_and_open_mp_tpu.models.life import LifeSim

    place = request.getfixturevalue(where)
    mesh = place if where == "mesh_2x2" else None
    sharding = NamedSharding(mesh, P("y", "x")) if mesh else place
    sim = object.__new__(LifeSim)
    sim.mesh = mesh
    sim.sharding = sharding if mesh else None
    board = jax.ShapeDtypeStruct((8192, 8192), jnp.uint8, sharding=sharding)
    sim.board = SimpleNamespace(shape=board.shape, sharding=sharding,
                                nbytes=8192 * 8192)
    compiled = sim._build_pack().lower(board).compile()
    out = compiled.output_shardings.shard_shape((8192, 256))
    assert out == ((4096, 128) if mesh else (8192, 256))
    text = compiled.as_text()
    for op in ("all-gather", "collective-permute", "all-to-all",
               "all-reduce", "reduce-scatter"):
        assert op not in text
