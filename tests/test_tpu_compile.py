"""Ask the chip's compiler, without the chip: the serving step variants and
the mesh variant compiled ahead of time for a DESCRIBED v5e:2x2 topology
(the TPU compiler ships with libtpu and needs no device attached). Nothing
runs, so these say nothing about results or speed — they catch what the CPU
backend cannot: a program the TPU compiler refuses, a step that stopped
aliasing its donated state, a collective that crept onto the groups axis.

Rules this file keeps (on-chip-measurement guide, section 2): the topology
is described inside a module-scoped fixture, never at import, so every
xdist worker collects the same tests and only the worker that runs this
file loads libtpu; compiles happen in this process; the persistent compile
cache is off around them (a described-device executable cannot be read
back without a chip); and all of them live in this one file. They compile
as a served member does — x64 off — not as the CPU suite runs.

Tier-1 keeps the small sizes (the structural floor is ~20 s per serving
variant even at G=128). The full-size compiles — G=12,500 per variant,
G=50,000 over four devices — take minutes and are marked slow; CHANGES.md
(PR 21) records their memory_analysis().
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, SingleDeviceSharding

from etcd_tpu.ops import kernel
from etcd_tpu.ops.state import KernelConfig, init_state
from etcd_tpu.server.engine import gather_program, step_program

P, W, HOPS = 5, 32, 3          # BASELINE.json config 4 peers; CLI window/hops
VARIANTS = ("step_routed_auto", "step_routed_compact",
            "step_routed_read_auto")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "not here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def as_served():
    """Compile the way a served member does, not the way the suite runs:
    x64 off (conftest turns it on for the CPU tests; a member never
    does), and the persistent cache off around these compiles."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    with jax.enable_x64(False):
        yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _shapes(cfg: KernelConfig, state_sh, inbox_sh, small_sh):
    """(state, inbox, prop, tick) as the engine's own entry points take
    them (engine.step_program: the staged proposals one (2, G) array), as
    shapes carrying shardings — there is no device to hold an array."""
    G = cfg.groups
    st = jax.eval_shape(lambda: init_state(cfg))
    if not isinstance(state_sh, tuple):      # one sharding for every field
        state_sh = jax.tree.map(lambda _: state_sh, st)
    st = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        st, state_sh)
    inbox = jax.ShapeDtypeStruct((G, cfg.peers, cfg.peers, cfg.fields),
                                 jnp.int32, sharding=inbox_sh)
    prop = jax.ShapeDtypeStruct((2, G), jnp.int32, sharding=small_sh)
    tick = jax.ShapeDtypeStruct((), jnp.bool_, sharding=small_sh)
    return st, inbox, prop, tick


def _compile_variant(topo, name: str, G: int, hold: bool = False,
                     down: bool = False, peers: int = P):
    """The variant as a TPU engine runs it (engine.step_program: the
    kernel's body behind the engine's entry point, the staged proposals one
    (2, G) array), state and inbox donated (the suite's JAX_PLATFORMS=cpu
    would leave them undonated, so donation is asked for here). `hold`:
    with the (G, P) bool of held follower slots as its one more argument
    (--engine-lag-share); `down`: with the (G, P) bool of slots cut off
    from their peers (--engine-churn-down-rounds), the hold then None."""
    cfg = KernelConfig(groups=G, peers=peers, window=W)
    one = SingleDeviceSharding(topo.devices[0])
    fn = step_program(name, cfg, HOPS, (0, 1))
    gp = jax.ShapeDtypeStruct((G, peers), jnp.bool_, sharding=one)
    return fn.lower(*_shapes(cfg, one, one, one), None,
                    gp if hold else None, gp if down else None).compile()


def _check_variant(compiled, G: int, peers: int = P) -> None:
    ma = compiled.memory_analysis()
    state_bytes = sum(
        int(np.prod(s.shape)) * s.dtype.itemsize
        for s in jax.eval_shape(
            lambda: init_state(KernelConfig(groups=G, peers=peers,
                                            window=W))))
    # Donation must really alias: the state arrays ARE the HBM budget, and
    # a step that copies them doubles it. Everything but scalars aliases.
    assert ma.alias_size_in_bytes >= state_bytes, (
        ma.alias_size_in_bytes, state_bytes)
    assert ma.temp_size_in_bytes + ma.argument_size_in_bytes < 16 << 30


@pytest.mark.parametrize("name", VARIANTS)
def test_serving_variant_compiles_for_v5e(topo, as_served, name):
    _check_variant(_compile_variant(topo, name, 128), 128)


@pytest.mark.parametrize("name", VARIANTS[1:])
def test_serving_variant_with_the_hold_compiles_for_v5e(topo, as_served,
                                                        name):
    """The two programs a member with --engine-lag-share serves."""
    _check_variant(_compile_variant(topo, name, 128, hold=True), 128)


@pytest.mark.parametrize("name", VARIANTS[1:])
def test_serving_variant_with_the_down_map_compiles_for_v5e(topo, as_served,
                                                            name):
    """The two programs a member with --engine-churn-down-rounds serves, at
    BASELINE.json configs[4]'s seven peers."""
    _check_variant(_compile_variant(topo, name, 128, down=True, peers=7),
                   128, peers=7)


def _mesh4(topo) -> Mesh:
    """The four described devices, groups axis 4, peers axis 1."""
    return Mesh(np.array(topo.devices).reshape(4, 1), ("groups", "peers"))


def _compile_mesh(topo, G: int, name: str = "step_routed_auto",
                  down: bool = False, peers: int = P):
    """The engine's mesh step (engine.step_program: out_shardings pinned,
    donated, the staged proposals and the tick replicated as a round's
    upload and the boot-time constants are placed);
    step_routed_compact adds the flag map, sharded like the state, and
    the replicated need-host attestation; step_routed_read_auto the read
    plane's two (G,) arrays, sharded on groups, and then those two.
    `down`: with the (G, P) bool of slots cut off from their peers as the
    engine hands it over (--engine-churn-down-rounds on a mesh: sharded
    like the state's (G, P) fields, the hold None)."""
    from etcd_tpu.parallel.mesh import (flag_sharding, group_sharding,
                                        mailbox_sharding,
                                        replicated_sharding, state_sharding)
    cfg = KernelConfig(groups=G, peers=peers, window=W)
    mesh = _mesh4(topo)
    st_sh, mb_sh = state_sharding(mesh), mailbox_sharding(mesh)
    rep = replicated_sharding(mesh)
    out_sh = (st_sh, mb_sh)
    if name == "step_routed_read_auto":
        out_sh += (group_sharding(mesh),) * 2
    if name != "step_routed_auto":
        out_sh += (flag_sharding(mesh), rep)
    out_sh += (rep,)                    # the hops' counts
    fn = step_program(name, cfg, HOPS, (0, 1), True, out_sh)
    down_map = (jax.ShapeDtypeStruct((G, peers), jnp.bool_,
                                     sharding=flag_sharding(mesh))
                if down else None)
    return fn.lower(*_shapes(cfg, st_sh, mb_sh, rep), None, None,
                    down_map).compile()


def _collectives(compiled) -> list:
    """[all-reduce lines]; asserts there is no other kind of collective."""
    text = compiled.as_text()
    for op in ("all-to-all", "all-gather", "collective-permute",
               "reduce-scatter"):
        assert f" {op}(" not in text and f" {op}-start(" not in text, op
    return [ln for ln in text.splitlines() if " all-reduce(" in ln
            or " all-reduce-start(" in ln]


def _check_mesh(compiled, scalars: int = HOPS) -> None:
    # Groups never talk to each other: nothing data-sized may cross the
    # groups axis. The only collective is the scalar all-reduce of the
    # count of busy groups that selects the lax.cond branch, one per hop
    # (the compact step folds its need-host attestation in one more). The
    # busy branch is the P passes by sender (by_sender): the loop of passes
    # by rank ends every pass on "does any receiver hold another message",
    # a collective of its own once the groups are sharded, so a mesh's
    # programs have no loop at all.
    reduces = _collectives(compiled)
    assert len(reduces) <= scalars, reduces
    assert all("[]" in ln.split("=", 2)[1] for ln in reduces), reduces
    assert " while(" not in compiled.as_text()


def test_mesh_variant_compiles_for_v5e_2x2(topo, as_served):
    _check_mesh(_compile_mesh(topo, 4))


def test_mesh_compact_variant_compiles_for_v5e_2x2(topo, as_served):
    """The flag map is computed shard by shard: the compact step adds one
    scalar all-reduce (any need-host) to the mesh step's, nothing else."""
    _check_mesh(_compile_mesh(topo, 4, "step_routed_compact"), HOPS + 1)


def test_mesh_read_variant_compiles_for_v5e_2x2(topo, as_served):
    """The read step's collectives are what they were before it returned
    the flag map (the quiet predicate's scalar, one per hop: the
    ReadIndex tally is per group) plus the attestation's scalar: nothing
    gathers the flag map or the (G,) confirmations (gather_rows does,
    in its own program)."""
    _check_mesh(_compile_mesh(topo, 4, "step_routed_read_auto"), HOPS + 1)


@pytest.mark.parametrize("name", VARIANTS[1:])
def test_mesh_variant_with_the_down_map_compiles_for_v5e_2x2(topo, as_served,
                                                             name):
    """The two programs a mesh member with --engine-churn-down-rounds serves
    (mt100k-p7-churn-mesh4, seven peers): the down map arrives sharded like
    the state and cuts shard by shard, so the collectives stay the hop's one
    scalar and the attestation's, and the seven passes by sender of a busy
    hop are unrolled: no loop."""
    _check_mesh(_compile_mesh(topo, 8, name, down=True, peers=7), HOPS + 1)


def _compile_need_host(topo, G: int, peers: int = 7):
    """The need-host surgery's two programs as a mesh engine builds them
    (engine.py: kernel.pick_groups with its rows replicated,
    kernel.put_groups donated and pinned to the fields' shardings), at the
    engine's NEED_HOST_GROUPS groups a pass."""
    from etcd_tpu.parallel.mesh import replicated_sharding, state_sharding
    from etcd_tpu.server.engine import NEED_HOST_GROUPS as K
    cfg = KernelConfig(groups=G, peers=peers, window=W)
    mesh = _mesh4(topo)
    st_sh, rep = state_sharding(mesh), replicated_sharding(mesh)
    st = _shapes(cfg, st_sh, rep, rep)[0]
    idx = jax.ShapeDtypeStruct((K,), jnp.int32, sharding=rep)
    read = tuple(getattr(st, f) for f in kernel.NEED_HOST_READ)
    pick = jax.jit(kernel.pick_groups,
                   out_shardings=rep).lower(read, idx).compile()
    write = tuple(getattr(st, f) for f in kernel.NEED_HOST_WRITE)
    rows = tuple(jax.ShapeDtypeStruct((K,) + x.shape[1:], x.dtype,
                                      sharding=rep) for x in write)
    put = jax.jit(
        kernel.put_groups, donate_argnums=(0, 1),
        out_shardings=(tuple(getattr(st_sh, f)
                             for f in kernel.NEED_HOST_WRITE),
                       st_sh.need_host)).lower(write, st.need_host, idx,
                                               rows).compile()
    return pick, put, K, write


def _check_need_host(pick, put, G: int, K: int, write) -> None:
    """No state array crosses the chips: every chip picks from the groups
    it holds and all-reduces K groups' rows (never G / 4), and the
    write-back scatters into each chip's own shard with no collective at
    all, in place (the donated fields alias)."""
    reduces = _collectives(pick)
    assert reduces and all(f"[{K}," in ln and f"[{G // 4}," not in ln
                           for ln in reduces), reduces
    assert _collectives(put) == []
    shard_bytes = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                      for x in write) // 4
    assert put.memory_analysis().alias_size_in_bytes >= shard_bytes
    assert pick.memory_analysis().output_size_in_bytes < 4 * K * 7 * 7 * 6 * 4


def test_need_host_programs_compile_for_v5e_2x2(topo, as_served):
    pick, put, K, write = _compile_need_host(topo, 512)
    _check_need_host(pick, put, 512, K, write)


def _lower_mesh_gather(topo, G: int, K: int):
    """The engine's mesh row gather (engine.gather_program: gather_rows'
    body, the packed buffer replicated) over the six fields it reads of a
    state sharded on four devices, the flag map as the compact step leaves
    it, the staged array replicated as the round's upload is placed."""
    from etcd_tpu.parallel.mesh import (flag_sharding, replicated_sharding,
                                        state_sharding)
    cfg = KernelConfig(groups=G, peers=P, window=W)
    mesh = _mesh4(topo)
    st_sh, rep = state_sharding(mesh), replicated_sharding(mesh)
    st, _, prop, attest = _shapes(cfg, st_sh, rep, rep)
    fields = tuple(getattr(st, f) for f in kernel.GATHER_FIELDS)
    flags = jax.ShapeDtypeStruct((G, P), jnp.uint8,
                                 sharding=flag_sharding(mesh))
    stats = jax.ShapeDtypeStruct((2, HOPS), jnp.int32, sharding=rep)
    return gather_program(rep).lower(fields, flags, attest, stats, prop, K)


def _compile_mesh_gather(topo, G: int, K: int):
    return _lower_mesh_gather(topo, G, K).compile()


def _check_mesh_gather(compiled, G: int, K: int) -> None:
    """ONE all-gather brings the (G, P) uint8 flag map's shards together
    for the pick, every chip gathers from the rows it holds and ONE
    all-reduce of the K gathered rows brings them together: nothing of
    the pick's sums and searches crosses the chips, and no all-gather of
    the sharded state (the ring alone is G*P*W*4 bytes), which is what
    would make the compact path cost more than the full readback it
    replaces."""
    text = compiled.as_text()
    for op in ("all-to-all", "collective-permute", "reduce-scatter"):
        assert f" {op}(" not in text and f" {op}-start(" not in text, op
    gathers = [ln for ln in text.splitlines()
               if " all-gather(" in ln or " all-gather-start(" in ln]
    assert len(gathers) <= 1, gathers
    assert all(f"u8[4,{G // 4},{P}]" in ln for ln in gathers), gathers
    reduces = [ln for ln in text.splitlines()
               if " all-reduce(" in ln or " all-reduce-start(" in ln]
    assert len(reduces) == 1, reduces
    assert f"s32[{K},{W}]" in reduces[0] and f"[{G}" not in reduces[0]
    # Temp memory within a small factor of what the program works on: the
    # flag map's bytes, the pick's (K, P) int32 and the K packed rows
    # (a fixed 0.6 MB up to K = 4,096 and 6.6 x at G = 50,000,
    # K = 32,768, rows padded to the lanes), so the ring's 32 MB gathered
    # there fails it at every bucket.
    ma = compiled.memory_analysis()
    need = G * P + 4 * K * P + 4 * K * (W + 7)
    assert ma.output_size_in_bytes < 8 * K * (W + 7) and \
        ma.temp_size_in_bytes < (1 << 20) + 8 * need


def test_mesh_gather_rows_compiles_for_v5e_2x2(topo, as_served):
    _check_mesh_gather(_compile_mesh_gather(topo, 128, 256), 128, 256)


def test_mesh_gather_rows_takes_ten_buffers(topo, as_served):
    """What a round hands the gather: the six fields it reads, the flag
    map, the attestation, the hops' counts and the one staged array, each
    on the sharding it already lies on (the fields and the flag map as the
    step's pinned outputs, the rest replicated), so the call moves
    nothing between the chips before the program runs."""
    from etcd_tpu.parallel.mesh import (flag_sharding, replicated_sharding,
                                        state_sharding)
    lowered = _lower_mesh_gather(topo, 128, 256)
    assert len(jax.tree.leaves(lowered.in_avals)) == 10
    mesh = _mesh4(topo)
    st_sh, rep = state_sharding(mesh), replicated_sharding(mesh)
    want = (tuple(getattr(st_sh, f) for f in kernel.GATHER_FIELDS),
            flag_sharding(mesh), rep, rep, rep)
    got = lowered.compile().input_shardings[0]
    for w, g, nd in zip(jax.tree.leaves(want), jax.tree.leaves(got),
                        (2, 2, 2, 2, 2, 3, 2, 0, 2, 2)):
        assert g.is_equivalent_to(w, nd), (g, w)


@pytest.mark.slow
@pytest.mark.parametrize("name", VARIANTS)
def test_serving_variant_full_size(topo, as_served, name):
    """One chip's share of config 4 (G=12,500): ~85 s per variant."""
    _check_variant(_compile_variant(topo, name, 12_500), 12_500)


@pytest.mark.slow
@pytest.mark.parametrize("name", VARIANTS[1:])
def test_serving_variant_with_the_hold_full_size(topo, as_served, name):
    """mt100k-p5-lag5's programs (G=12,500 with the hold)."""
    _check_variant(_compile_variant(topo, name, 12_500, hold=True), 12_500)


@pytest.mark.slow
@pytest.mark.parametrize("name", VARIANTS[1:])
def test_serving_variant_with_the_down_map_full_size(topo, as_served, name):
    """mt100k-p7-churn's programs (G=12,500 x P=7 with the down map)."""
    _check_variant(_compile_variant(topo, name, 12_500, down=True, peers=7),
                   12_500, peers=7)


@pytest.mark.slow
@pytest.mark.parametrize("name,scalars", [
    ("step_routed_auto", HOPS), ("step_routed_compact", HOPS + 1),
    ("step_routed_read_auto", HOPS + 1)])
def test_mesh_variant_full_size(topo, as_served, name, scalars):
    """G=50,000 over four devices (what chip_smoke.py --chips 4 and the
    cell mesh50k.put256-c256 serve)."""
    compiled = _compile_mesh(topo, 50_000, name)
    _check_mesh(compiled, scalars)
    ma = compiled.memory_analysis()    # per device
    assert ma.temp_size_in_bytes + ma.argument_size_in_bytes < 16 << 30


@pytest.mark.slow
@pytest.mark.parametrize("name", VARIANTS[1:])
def test_mesh_variant_with_the_down_map_full_size(topo, as_served, name):
    """mt100k-p7-churn-mesh4's programs: G=50,000 x P=7 over four devices,
    12,500 rows a device, with the down map (the cell
    meshchurn50k.put256-zipf)."""
    compiled = _compile_mesh(topo, 50_000, name, down=True, peers=7)
    _check_mesh(compiled, HOPS + 1)
    ma = compiled.memory_analysis()    # per device
    assert ma.temp_size_in_bytes + ma.argument_size_in_bytes < 16 << 30


@pytest.mark.slow
def test_need_host_programs_full_size(topo, as_served):
    """mt100k-p7-churn-mesh4's surgery: G=50,000 x P=7 over four devices."""
    pick, put, K, write = _compile_need_host(topo, 50_000)
    _check_need_host(pick, put, 50_000, K, write)


@pytest.mark.slow
@pytest.mark.parametrize("K", [256, 32_768])
def test_mesh_gather_rows_full_size(topo, as_served, K):
    """G=50,000: the smallest bucket and the one that holds the auto cap
    (max(2048, G*P//8) = 31,250 rows)."""
    _check_mesh_gather(_compile_mesh_gather(topo, 50_000, K), 50_000, K)
