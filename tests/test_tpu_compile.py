"""Compile the main-path kernels for a DESCRIBED TPU v5e (2x2), no chip
attached: what the chip's compiler would refuse — a misaligned slice,
too much VMEM, a program that cannot be partitioned — fails here, at no
chip time. Shapes are BASELINE config 1's (1000 histories x 1k ops:
W8, S8, B1024, E1760) and the ones chip_smoke.py launches.

A compile that passes is not a chip run: nothing executes, and nothing
here is a speed statement.

The topology is described inside a module-scoped fixture, never at
import: only one process may hold the TPU compiler's library, and every
xdist worker imports every test file. The compiles run in this process
(a child could not load the library either), with the persistent
compilation cache off — an executable compiled for a described device
cannot be read back without one.

`jax.default_backend()` still answers "cpu" here, so the TPU-side
branches are forced through the knobs that exist (JGRAFT_HOIST,
JGRAFT_MERGE_LONG).
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from jepsen_jgroups_raft_tpu.checker.schedule import (DEFAULT_SCAN_CHUNK,
                                                      long_width)
from jepsen_jgroups_raft_tpu.models.counter import Counter
from jepsen_jgroups_raft_tpu.models.register import CasRegister
from jepsen_jgroups_raft_tpu.ops.dense_scan import (make_dense_batch_checker,
                                                    make_dense_chunk_checker)
from jepsen_jgroups_raft_tpu.ops.kernel_ir import (CYCLE_TILE,
                                                   SORT_DEFAULT_CONFIGS,
                                                   macro_row_ints,
                                                   make_cycle_closure,
                                                   make_cycle_closure_tiled,
                                                   make_txn_closure)
from jepsen_jgroups_raft_tpu.ops.linear_scan import (make_batch_checker,
                                                     make_sort_chunk_checker)
from jepsen_jgroups_raft_tpu.parallel.mesh import BATCH_AXIS

# BASELINE config 1 as the checker buckets it
W, S, B, E = 8, 8, 1024, 1760
MACRO_P = 4


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    assert len(topo.devices) == 4
    mesh = Mesh(np.asarray(topo.devices), (BATCH_AXIS,))
    return mesh, NamedSharding(mesh, P(BATCH_AXIS))


@pytest.fixture
def tpu_branches(monkeypatch):
    """The branches `jax.default_backend() == "tpu"` takes on the chip."""
    monkeypatch.setenv("JGRAFT_HOIST", "1")
    monkeypatch.setenv("JGRAFT_MERGE_LONG", "1")


def sds(shape, sharding, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def compile_for(fn, *args):
    compiled = fn.lower(*args).compile()
    assert compiled.memory_analysis() is not None
    return compiled


def row_ints(macro_p):
    return 5 if macro_p is None else macro_row_ints(macro_p)


@pytest.mark.parametrize("macro_p", [None, MACRO_P],
                         ids=["legacy", "macro"])
@pytest.mark.parametrize("kind", ["domain", "mask"])
def test_dense_batch_kernel_compiles(one_chip, tpu_branches, kind, macro_p):
    model, n_states = ((CasRegister(), S) if kind == "domain"
                       else (Counter(), 1))
    fn = make_dense_batch_checker(model, kind, W, n_states, macro_p=macro_p)
    compile_for(fn, sds((B, E, row_ints(macro_p)), one_chip),
                sds((B, n_states), one_chip))


@pytest.mark.parametrize("n_configs,n_slots",
                         [(64, 8), (SORT_DEFAULT_CONFIGS, 16)],
                         ids=["first-rung", "top-rung"])
def test_sort_ladder_kernel_compiles(one_chip, n_configs, n_slots):
    # windows as chip_smoke's sort batch has them: <=8 on the first
    # rung, the wide rows that escalate bucketed exact up to 16
    fn = make_batch_checker(CasRegister(), n_configs, n_slots)
    compile_for(fn, sds((64, 330, 5), one_chip))


def carry_like(lowered, sharding):
    """The chunk kernels' carry as `init_fn` shapes it, placed like the
    batch (every leaf is batch-leading)."""
    return jax.tree_util.tree_map(
        lambda x: sds(x.shape, sharding, x.dtype), lowered.out_info)


def compile_chunk_pair(fns, init_args, events):
    init_fn, step_fn = fns
    lowered = init_fn.lower(*init_args)
    lowered.compile()
    # a span's offset and length are traced scalars of the step program
    return compile_for(step_fn, carry_like(lowered, events.sharding),
                       events, np.int32(0), np.int32(events.shape[1]))


def test_sort_chunk_pair_compiles(one_chip):
    # the sort rung as the wavefront scheduler launches it
    fns = make_sort_chunk_checker(CasRegister(), 64, 8, macro_p=MACRO_P)
    compile_chunk_pair(
        fns, (sds((32,), one_chip),),
        sds((32, DEFAULT_SCAN_CHUNK, row_ints(MACRO_P)), one_chip))


@pytest.mark.parametrize("kind", ["domain", "mask"])
def test_dense_chunk_pair_compiles_on_one_chip(one_chip, tpu_branches, kind):
    # the default production shape: one window group, one chunk
    model, n_states = ((CasRegister(), S) if kind == "domain"
                       else (Counter(), 1))
    fns = make_dense_chunk_checker(model, kind, W, n_states,
                                   macro_p=MACRO_P)
    compile_chunk_pair(
        fns, (sds((512, n_states), one_chip), sds((512,), one_chip)),
        sds((512, DEFAULT_SCAN_CHUNK, row_ints(MACRO_P)), one_chip))


def test_packed_domain_step_compiles_at_the_widest_window(one_chip,
                                                          tpu_branches):
    # ISSUE 41: the packed frontier at the caps' corner the partition
    # cell serves (W 13, S 8, a 128-row group): a uint32 word a
    # configuration in and out, and no matmul or convert left in the
    # step (the float sweep lowered its W `dot`s to convolutions)
    from jepsen_jgroups_raft_tpu.ops.kernel_ir import DENSE_MAX_SLOTS

    assert DENSE_MAX_SLOTS == 13
    fns = make_dense_chunk_checker(CasRegister(), "domain",
                                   DENSE_MAX_SLOTS, S, macro_p=MACRO_P)
    compiled = compile_chunk_pair(
        fns, (sds((128, S), one_chip), sds((128,), one_chip)),
        sds((128, DEFAULT_SCAN_CHUNK, row_ints(MACRO_P)), one_chip))
    text = compiled.as_text()
    assert "u32[128,8192]" in text and "pred[128,8192,8]" not in text
    assert " convolution(" not in text and " dot(" not in text
    assert "f32[" not in text


def loop_bodies(compiled) -> list:
    """What each `while` body of a compiled program holds, as sorted
    (instruction, count) tuples, bookkeeping instructions apart."""
    import collections
    import re

    text = compiled.as_text()
    computations = [c.strip() for c in text.split("\n\n")]
    bodies = []
    for body in re.findall(r"while\(.*?\), condition=%?[\w.-]+, "
                           r"body=%?([\w.-]+)", text):
        [comp] = [c for c in computations
                  if c.startswith(("%" + body + " ", body + " "))]
        ops = collections.Counter(
            m.group(1) for line in comp.split("\n")
            for m in [re.search(r"= \S+ ([a-z][\w-]*)\(",
                                re.sub(r"\{[^{}]*\}", "", line))] if m)
        bodies.append(tuple(sorted(
            (k, v) for k, v in ops.items()
            if k not in ("get-tuple-element", "parameter", "tuple",
                         "bitcast", "constant"))))
    return sorted(bodies)


def test_a_bucket_of_the_shared_trace_is_the_same_scan(one_chip,
                                                       tpu_branches):
    # ISSUE 43: a row bucket's step is the key's one lowering (rows
    # symbolic, `jax.export`) called at the bucket's row count. At the
    # partition cell's W 12 x 128 rows it must compile to the loops the
    # step's own jit compiles to: the closure's sweep PR 41 counted
    # (10 fusions, 15 slices, 1 copy), the same two `while` bodies (the
    # span and the closure; until ISSUE 45 a third, FORCE's gather run
    # as a loop over the rows)
    from jax import export

    rows, w = 128, 12
    init_fn, step_fn = make_dense_chunk_checker(CasRegister(), "domain", w,
                                                S, macro_p=MACRO_P)
    carry = carry_like(init_fn.lower(sds((rows, S), one_chip),
                                     sds((rows,), one_chip)), one_chip)
    args = (carry, sds((rows, 1024, row_ints(MACRO_P)), one_chip),
            np.int32(0), np.int32(1024))
    (b,) = export.symbolic_shape("rows")
    scalar = jax.ShapeDtypeStruct((), jnp.int32)

    def any_rows(x):
        return jax.ShapeDtypeStruct((b,) + x.shape[1:], x.dtype)

    exported = export.export(step_fn, platforms=["tpu"])(
        jax.tree_util.tree_map(any_rows, carry), any_rows(args[1]),
        scalar, scalar)
    own = loop_bodies(compile_for(step_fn, *args))
    shared = loop_bodies(compile_for(jax.jit(exported.call), *args))
    assert own == shared and len(own) == 2
    assert (("copy", 1), ("fusion", 10), ("slice", 15)) in shared


@pytest.mark.parametrize("kind", ["domain", "mask"])
def test_batched_step_holds_the_span_and_the_closure_and_no_row_loop(
        one_chip, tpu_branches, kind):
    # ISSUE 45: the W 8 keys of the three batched cells at 128 rows.
    # FORCE's down-shift was a `dynamic_slice` with a start a row: a
    # gather under `vmap`, which this compiler ran as a third `while`
    # over the rows of the launch (a dynamic-slice and a
    # dynamic-update-slice an iteration: `while.43` / `while.29` of the
    # ledger's breakdowns, 73 % of the device's time). Now W static
    # slices selected by the slot: two loops, no gather, and the one
    # dynamic slice left in either body is the event fetch (the mask
    # sweep's concatenate is a dynamic-update-slice at constants)
    import re

    model, n_states = ((CasRegister(), S) if kind == "domain"
                       else (Counter(), 1))
    fns = make_dense_chunk_checker(model, kind, W, n_states,
                                   macro_p=MACRO_P)
    compiled = compile_chunk_pair(
        fns, (sds((128, n_states), one_chip), sds((128,), one_chip)),
        sds((128, 2048, row_ints(MACRO_P)), one_chip))
    text = compiled.as_text()
    assert len(loop_bodies(compiled)) == 2
    assert " gather(" not in text
    fetches = re.findall(
        r" dynamic-slice\(.*?dynamic_slice_sizes=\{([\d,]+)\}", text)
    assert set(fetches) <= {f"128,1,{row_ints(MACRO_P)}"}, fetches


def test_dense_chunk_pair_compiles_on_four_chip_mesh(mesh4, tpu_branches):
    # parallel/mesh.chunk_sharding's layout: rows over a 1-D mesh, the
    # kernels wrapped in an explicit batch-axis shard_map
    mesh, rows = mesh4
    fns = make_dense_chunk_checker(CasRegister(), "domain", W, S,
                                   mesh=mesh, macro_p=MACRO_P)
    compiled = compile_chunk_pair(
        fns, (sds((512, S), rows), sds((512,), rows)),
        sds((512, DEFAULT_SCAN_CHUNK, row_ints(MACRO_P)), rows))
    # per-row work only: no collective may appear in the step program
    text = compiled.as_text()
    assert "all-reduce" not in text and "all-gather" not in text


@pytest.mark.parametrize("w,macro_p", [(5, 6), (6, 6), (7, 8), (8, 8)],
                         ids=["W5", "W6", "W7", "W8"])
def test_a_long_launch_compiles_at_the_long_cells_shapes(one_chip,
                                                         tpu_branches,
                                                         w, macro_p):
    # register-100k.long-run's four LONG keys as a host's
    # `launch-keys.json` records them (PERF.md section 4): ONE row, S 8,
    # the window's payload bucket, and a 100k-op history's 146,282
    # events as ~half as many macro steps on the LONG width ladder. A
    # LONG key is `init` and `step`: it never recompacts
    width = long_width(146_282 // 2)
    assert width == 73_728
    fns = make_dense_chunk_checker(CasRegister(), "domain", w, S,
                                   macro_p=macro_p)
    compiled = compile_chunk_pair(
        fns, (sds((1, S), one_chip), sds((1,), one_chip)),
        sds((1, width, row_ints(macro_p)), one_chip))
    assert f"u32[1,{1 << w}]" in compiled.as_text()


@pytest.mark.parametrize("n_nodes,tiled", [(256, False), (768, True)],
                         ids=["monolithic-256", "tiled-768"])
def test_cycle_closure_kernel_compiles(one_chip, n_nodes, tiled):
    fn = (make_cycle_closure_tiled(n_nodes, CYCLE_TILE) if tiled
          else make_cycle_closure(n_nodes))
    compile_for(fn, sds((8, n_nodes, n_nodes), one_chip))


def test_txn_closure_program_compiles_at_the_cells_node_bucket(one_chip):
    """The closure program of `list-append-1k.campaign-txn` (ISSUE 51):
    N 1,024, sixteen edges a node, the smallest row bucket (the cell's
    64 rows compile the same program in 15 s with 2.35 GB of
    temporaries). It holds the scatter, and the three closures' loops
    around an `s32` product."""
    compiled = compile_for(make_txn_closure(1024),
                           sds((8, 16384), one_chip))
    text = compiled.as_text()
    assert "scatter(" in text
    assert text.count("s32[8,1024,1024]") >= 3
