"""Compile-only rehearsal of the solver path for a TPU v5e (no chip needed).

The TPU compiler is installed with jaxlib, so these tests compile the
real-size programs ``chip_smoke.py`` runs — for a described ``v5e:2x2``
topology, from shapes alone — and assert that every pallas unit reached
Mosaic as a ``tpu_custom_call``.  They catch what interpret mode cannot:
layouts Mosaic refuses, scalars outside SMEM, VMEM over the scoped
limit.  Nothing here executes, so nothing here measures speed.

The topology is described inside a module fixture (never at import): only
one process may load the TPU library at a time, and the worker that runs
this file keeps it until it exits.
"""
import re

import numpy as np
import pytest

from repro.api import Session
from repro.exec import get_backend


@pytest.fixture(scope="module")
def topo():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                       # pragma: no cover
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip cannot be read back from the
    # persistent cache here: keep them out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture
def mosaic(monkeypatch):
    """Compile pallas kernels through Mosaic even though the host is CPU."""
    monkeypatch.setenv("TPU_LOG_DIR", "disabled")
    monkeypatch.setenv("CELLO_PALLAS_INTERPRET", "0")


def _plan(workload, mesh=None, **params):
    sess = Session(use_cache=False)
    traced = sess.trace(workload=workload, **params)
    return traced, sess.lower(sess.codesign(traced), backend="pallas",
                              mesh=mesh)


def _shapes(program, names, sharding, batch=None):
    import jax
    import jax.numpy as jnp
    out = []
    for n in names:
        nd = program.nodes[n]
        dtype = (jnp.int32 if nd.param("role") in ("indptr", "indices")
                 else jnp.float32)
        shape = tuple(nd.shape) if batch is None \
            else (batch,) + tuple(nd.shape)
        out.append(jax.ShapeDtypeStruct(shape, dtype, sharding=sharding))
    return out


def _assert_every_kernel_compiled(prog, text):
    """Each stream/block unit the program traces is a Mosaic custom call
    (kernels are named ``cello_<kind>_<first op>``, ``cello_wide_`` for a
    column-blocked pass, ``cello_lanes_`` for a batched pass that read its
    operand once for all lanes, ``vmap_``-prefixed when batched).  A pass
    whose values nothing reads emits no kernel."""
    from repro.exec.pallas import _BlockCall, _StreamCall
    calls = {m for line in text.splitlines() if "tpu_custom_call" in line
             for m in re.findall(r"%(?:vmap_)?(cello_\w+?)_?(?:\.\d+)? = ",
                                 line)}
    units = (*prog._pro, *prog._tmpl, *prog._epi)
    want = {c.lanes_kernel_name if c.lanes and c.batched_read == "shared"
            else c.kernel_name for c in units
            if isinstance(c, _StreamCall) and (c.red_out or c.stream_out)}
    want |= {"cello_block_" + c.nodes[0].name for c in units
             if isinstance(c, _BlockCall)}
    assert want, "plan has no pallas units"
    assert want <= calls, sorted(want - calls)


def _while_bodies(text):
    """The HLO text of every computation a ``while`` loop of a compiled
    module runs: its bodies and whatever they call, transitively."""
    comps, name = {}, None
    for line in text.splitlines():
        m = re.match(r"^(?:ENTRY\s+)?%([\w.\-]+)\s.*\{\s*$", line)
        if m:
            name = m.group(1)
            comps[name] = []
        elif name is not None:
            comps[name].append(line)
    calls = re.compile(r"(?:body|calls|to_apply|condition|branch_computations"
                       r"|called_computations)=\{?([%\w.\-, ]+)")

    def callees(comp):
        return {c.strip().lstrip("%") for line in comps.get(comp, ())
                for m in calls.finditer(line) for c in m.group(1).split(",")}

    todo = [m.group(1) for line in text.splitlines()
            for m in re.finditer(r"body=%([\w.\-]+)", line)]
    seen = set()
    while todo:
        c = todo.pop()
        if c not in seen:
            seen.add(c)
            todo.extend(callees(c))
    return "\n".join(line for c in seen for line in comps.get(c, ()))


#: the dense n=8192 plan's passes, as (tile rows, planned VMEM bytes,
#: resident operands, spmv) -> count: the sparse layouts leave them alone
DENSE_8192_PASSES = {(256, 17334272, 1, False): 1,
                     (256, 17350656, 1, False): 1,
                     (256, 17383424, 1, False): 31,
                     (1024, 196608, 0, False): 63,
                     (1024, 393216, 0, False): 1}


def test_dense_cg_n8192_compiles(topo, mosaic):
    from collections import Counter

    from jax.sharding import SingleDeviceSharding
    traced, plan = _plan("cg", n=8192, iters=32)
    assert all(u.kind == "stream" for u in plan.exec_plan.units)
    assert Counter((u.sp.tile_rows, u.sp.vmem_bytes, len(u.sp.resident),
                    bool(u.sp.spmv))
                   for u in plan.exec_plan.units) == DENSE_8192_PASSES
    prog = get_backend("pallas").compile(plan)
    args = _shapes(traced.program, prog.leaf_names,
                   SingleDeviceSharding(topo.devices[0]))
    compiled = prog._jit.lower(*args).compile()
    _assert_every_kernel_compiled(prog, compiled.as_text())


def test_sparse_cg_n1m_compiles(topo, mosaic):
    from jax.sharding import SingleDeviceSharding
    traced, plan = _plan("cg_sparse", n=1 << 20, iters=32)
    # every unit streams, the spmv ones on the diagonal layout (the
    # Laplacian's rows all draw from the offsets -1024, -1, 0, 1, 1024)
    assert all(u.kind == "stream" for u in plan.exec_plan.units)
    spmv_ops = {nd.name for nd in traced.program.nodes.values()
                if nd.op == "spmv"}
    assert spmv_ops
    assert spmv_ops <= {o for u in plan.exec_plan.units
                        for o in u.sp.dia}
    prog = get_backend("pallas").compile(plan)
    assert prog.spmv_layouts == {"dia": 33}
    args = _shapes(traced.program, prog.leaf_names,
                   SingleDeviceSharding(topo.devices[0]))
    compiled = prog._jit.lower(*args).compile()
    text = compiled.as_text()
    _assert_every_kernel_compiled(prog, text)
    assert "cello_dia_A" in text                 # the layout's own pass
    # nothing in the CG loop gathers x by column index
    body = _while_bodies(text)
    assert "cello_stream_Ap" in body
    assert not re.search(r"\sgather\(", body)


def test_batched_core_b8_compiles(topo, mosaic):
    from jax.sharding import SingleDeviceSharding
    traced, plan = _plan("cg", n=4096, iters=16)
    bp = plan.batched()
    one = SingleDeviceSharding(topo.devices[0])
    shared = _shapes(traced.program, bp.shared_leaves, one)
    batched = _shapes(traced.program, bp.batched_leaves, one, batch=8)
    text = bp._build().lower(shared, batched).compile().as_text()
    # every pass reads the operand once for all 8 lanes
    prog = bp._single.program
    assert set(prog.batched_passes()) == {"shared"}
    _assert_every_kernel_compiled(prog, text)
    assert "cello_lanes_Ap" in text and "cello_stream_" not in text


@pytest.mark.parametrize("lanes", [1, 2, 4, 8])
def test_batched_dense_core_served_widths_compile(topo, mosaic, lanes):
    """The served n=8192 plan at each padded batch width: one lane kernel
    per pass, within the scoped VMEM limit."""
    from jax.sharding import SingleDeviceSharding
    traced, plan = _plan("cg", n=8192, iters=32)
    bp = plan.batched()
    one = SingleDeviceSharding(topo.devices[0])
    shared = _shapes(traced.program, bp.shared_leaves, one)
    batched = _shapes(traced.program, bp.batched_leaves, one, batch=lanes)
    text = bp._build().lower(shared, batched).compile().as_text()
    prog = bp._single.program
    assert prog.batched_passes() == {"shared": 96}
    _assert_every_kernel_compiled(prog, text)
    assert "cello_stream_" not in text


def test_batched_sparse_core_b8_compiles(topo, mosaic):
    """A batch of right-hand sides against one Laplacian: the diagonal
    layout is built once, unbatched, and each lane kernel reads it once
    for all lanes."""
    from jax.sharding import SingleDeviceSharding
    traced, plan = _plan("cg_sparse", n=65536, iters=8)
    bp = plan.batched()
    one = SingleDeviceSharding(topo.devices[0])
    shared = _shapes(traced.program, bp.shared_leaves, one)
    batched = _shapes(traced.program, bp.batched_leaves, one, batch=8)
    text = bp._build().lower(shared, batched).compile().as_text()
    prog = bp._single.program
    assert prog.spmv_layouts == {"dia": 9}
    assert set(prog.batched_passes()) == {"shared"}
    _assert_every_kernel_compiled(prog, text)
    assert "%cello_dia_A" in text and "vmap_cello_dia" not in text


def _sharded_compile(topo, monkeypatch, traced, plan):
    """The sharded program of ``plan`` compiled for the described chips,
    every leaf at its traced shape on the mesh of its in_spec."""
    import jax
    from jax.sharding import Mesh, NamedSharding

    import repro.exec.sharded as sharded_mod
    devices = np.array(topo.devices[:plan.sharded.n_shards])
    # the executor builds its mesh from jax.devices(); hand it the
    # described chips instead
    monkeypatch.setattr(sharded_mod, "make_solver_mesh",
                        lambda n, axis="shards": Mesh(devices[:n], (axis,)))
    prog = sharded_mod.ShardedProgram(plan)
    mesh = Mesh(devices, (plan.sharded.axis,))
    _, in_specs, _, _ = sharded_mod._partition_specs(traced.program,
                                                     plan.sharded)
    args = [jax.ShapeDtypeStruct(a.shape, a.dtype,
                                 sharding=NamedSharding(mesh, spec))
            for a, spec in zip(_shapes(traced.program, prog.leaf_names,
                                       None), in_specs)]
    return prog, prog._jit.lower(*args).compile()


def test_sharded_dense_cg_n65536_mesh4_compiles(topo, mosaic, monkeypatch):
    """A dense operator larger than one chip (16 GiB in float32), 4 GiB a
    chip: every matvec pass is column-blocked (no jnp fallback holds a
    contraction), each kernel reaches Mosaic within the scoped VMEM
    limit, and the exchanges are collectives."""
    from repro.core.lowering import KERNEL_VMEM_BYTES
    from repro.exec.pallas import _vmem_limit
    traced, plan = _plan("cg", mesh=4, n=65536, iters=50)
    for units in (plan.exec_plan.units, plan.sharded.local.units):
        assert all(u.kind == "stream" or all(
            traced.program.nodes[o].op not in ("matmul", "einsum")
            for o in u.ops) for u in units)
    wide = [u.sp for u in plan.sharded.local.units
            if u.kind == "stream" and u.sp.tile_cols]
    assert wide and all(sp.rows == 16384 and 65536 % sp.tile_cols == 0
                        and sp.tile_cols >= 2048 for sp in wide)
    assert "/8192c" in plan.explain()
    assert all(u.sp.vmem_bytes <= KERNEL_VMEM_BYTES
               and u.sp.vmem_bytes < _vmem_limit(u.sp.vmem_bytes)
               for u in plan.sharded.local.units if u.kind == "stream")
    prog, compiled = _sharded_compile(topo, monkeypatch, traced, plan)
    assert prog.matvec_tilings == {"blocked": 51}
    text = compiled.as_text()
    _assert_every_kernel_compiled(prog, text)
    assert "cello_wide_Ap" in text
    assert "all-gather" in text and "all-reduce" in text
    # the operator's shard is the program's one large argument: 4 GiB a
    # chip, and no copy of it
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes < (4 << 30) + (8 << 20)
    assert mem.temp_size_in_bytes < (64 << 20)


def test_sharded_cg_4_devices_compiles(topo, mosaic, monkeypatch):
    import jax
    from jax.sharding import Mesh, NamedSharding

    import repro.exec.sharded as sharded_mod
    traced, plan = _plan("cg", mesh=4, n=8192, iters=32)
    devices = np.array(topo.devices[:4])
    # the executor builds its mesh from jax.devices(); hand it the
    # described chips instead
    monkeypatch.setattr(sharded_mod, "make_solver_mesh",
                        lambda n, axis="shards": Mesh(devices[:n], (axis,)))
    prog = sharded_mod.ShardedProgram(plan)
    mesh = Mesh(devices, (plan.sharded.axis,))
    _, in_specs, _, _ = sharded_mod._partition_specs(traced.program,
                                                     plan.sharded)
    args = [jax.ShapeDtypeStruct(a.shape, a.dtype,
                                 sharding=NamedSharding(mesh, spec))
            for a, spec in zip(_shapes(traced.program, prog.leaf_names,
                                       None), in_specs)]
    compiled = prog._jit.lower(*args).compile()
    text = compiled.as_text()
    _assert_every_kernel_compiled(prog, text)
    assert "all-gather" in text and "all-reduce" in text
