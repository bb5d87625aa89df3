"""Compile the slab data plane for a described TPU v5e; no chip needed.

The TPU compiler ships with JAX: ``topologies.get_topology_desc``
describes a v5e 2x2 host that is not attached, and
``jit(...).lower(...).compile()`` then refuses what the chip's compiler
would refuse — slices not aligned to the tiling, blocks larger than
VMEM, programs larger than HBM — none of which interpret mode sees.
The shapes are the real ones: the rows, payloads and offsets of a
4-rank dispatch plan at the row widths of mixtral-8x7b (4096) and
deepseek-moe-16b (2048), in bf16, and at the MoE widths the kernels take
only lane-padded (``ops.lane_width``): 2688 (Nemotron-3-Nano-30B-A3B),
2304, 2560, 3584, 4608 and 7680.

The topology is described in a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and under
pytest-xdist every worker imports this file.
"""
import functools
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

import chip_smoke
from repro.core import jax_collectives as jc
from repro.kernels.ragged_gather import ops
from repro.kernels.ragged_gather.kernel import KERNEL_NAMES

HBM_BYTES = 16 * 2**30          # one v5e chip
WIDTHS = {"mixtral-8x7b": 4096, "deepseek-moe-16b": 2048}
# MoE hidden sizes of bf16 rows whose lane-group count is not a multiple
# of 8: the kernels run them at ``ops.lane_width``
PADDED_WIDTHS = (2688, 2304, 2560, 3584, 4608, 7680)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip cannot be read back from the cache
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # pragma: no cover - depends on the install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    return Mesh(np.asarray(topo.devices).reshape(4), ("x",))


@pytest.fixture(scope="module")
def pallas():
    prev = jc.dataplane()
    jc.set_dataplane("pallas")
    yield
    jc.set_dataplane(prev)


def _plan(model: str, op: str):
    """The plan the smoke run executes for ``op``: the alltoallv of the
    dispatch matrix, the others over the rows each expert chip holds."""
    S = chip_smoke.dispatch_matrix(model, 4, chip_smoke.TOKENS_PER_CHIP)
    sizes = S.sum(axis=0)
    return {"gatherv": lambda: jc.plan_gatherv(sizes, 0),
            "scatterv": lambda: jc.plan_gatherv(sizes, 0),
            "allgatherv": lambda: jc.plan_allgatherv(sizes),
            "alltoallv": lambda: jc.plan_alltoallv(S),
            "reduce_scatterv": lambda: jc.plan_reduce_scatterv(sizes),
            "allreducev": lambda: jc.plan_allreducev(sizes)}[op]()


def _fits(compiled) -> int:
    ma = compiled.memory_analysis()
    used = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert used <= HBM_BYTES, used
    return used


RELAYOUT_OPCODES = ("copy", "copy-start", "transpose")
_INSTR = re.compile(r"\s*(ROOT )?%([\w.\-]+) = \w+\[([\d,]*)\]\S* "
                    r"([\w\-]+)\(")


def _computations(hlo: str) -> dict:
    """Compiled HLO text as ``{computation: [(name, opcode, elements,
    called computation, is root)]}``; ``"ENTRY"`` names the entry
    computation.  Tuple-valued instructions are left out."""
    comps, body = {}, None
    for line in hlo.splitlines():
        head = re.match(r"(ENTRY )?%([\w.\-]+) \(", line)
        if head:
            body = comps.setdefault("ENTRY" if head.group(1)
                                    else head.group(2), [])
        elif body is not None and (m := _INSTR.match(line)):
            root, name, dims, opcode = m.groups()
            n = int(np.prod([int(d) for d in dims.split(",") if d]))
            calls = re.search(r"calls=%([\w.\-]+)", line)
            body.append((name, opcode, n, calls and calls.group(1),
                         bool(root)))
    return comps


def _whole_buffer_ops(hlo: str, elements: int, opcodes=RELAYOUT_OPCODES,
                      *, fused: bool = True) -> list[str]:
    """Entry instructions that are one of ``opcodes`` over an array of at
    least ``elements``, alone or inside a fusion: by default the
    relayouts (copies, transposes) of a whole buffer.  Where the
    buffer's rows are not a multiple of the tile's 8, its relayout also
    pads or slices it by a few rows; those ops belong to the copy and are
    not counted apart.  ``fused=False`` counts a fusion only by its root,
    the array it writes: a scalar that a fused ``select`` broadcasts is
    no array of its own.  Kernels are custom-calls and never count."""
    comps = _computations(hlo)

    def inside(calls):
        return [ins for ins in comps.get(calls, []) if fused or ins[4]]

    return [f"{name} ({opcode})"
            for name, opcode, n, calls, _ in comps["ENTRY"]
            if any(op in opcodes and m >= elements
                   for _, op, m, *_ in [(name, opcode, n)]
                   + (inside(calls) if opcode == "fusion" else []))]


def _slab_program(op: str, F: int, sharding):
    """``(fn, args)``: slab kernel ``op`` at the buffer, payloads and
    offsets of the first transfer of the mixtral dispatch plan whose send
    and receive rows are both unaligned, at row width ``F``;
    ``slab_fill`` at the sender's input and input offset, ``slab_unpack``
    at the receiver's blocks and output."""
    plan = _plan("mixtral-8x7b", "alltoallv")
    k, src, dst = next((k, s, d) for k, step in enumerate(plan.steps[:-1])
                       for s, d in step[0]
                       if step[2][s] % 8 and step[3][d] % 8)
    (perm, payload, send, recv, valid), nxt = plan.steps[k:k + 2]
    row = ops.row_view(jnp.zeros((1, ops.lane_width(F, jnp.bfloat16)))
                       ).shape[1:]

    def shape(rows):
        return jax.ShapeDtypeStruct((rows,) + row, jnp.bfloat16,
                                    sharding=sharding)

    fn = getattr(ops, op)
    body = {
        "slab_extract": lambda b, s: fn(b, int(send[src]), payload,
                                        interpret=False),
        "slab_merge": lambda b, s: fn(b, s, int(recv[dst]), int(valid[dst]),
                                      interpret=False),
        "slab_merge_add": lambda b, s: fn(b, s, int(recv[dst]),
                                          int(valid[dst]), interpret=False),
        "slab_step": lambda b, s: fn(b, s, int(recv[dst]), int(valid[dst]),
                                     int(nxt[2][dst]), nxt[1],
                                     interpret=False),
        "slab_step_reduce": lambda b, s: fn(b, s, int(recv[dst]),
                                            int(valid[dst]),
                                            int(nxt[2][dst]), nxt[1],
                                            interpret=False),
        "slab_fill": lambda x: fn(x, plan.buf_rows, plan.in_starts[src],
                                  interpret=False),
        "slab_unpack": lambda b: fn(b, *([int(t[dst]) for t in tables]
                                         for tables in zip(*plan.extract)),
                                    plan.chunk, plan.out_rows,
                                    interpret=False),
    }[op]
    if op == "slab_fill":   # the sender's input into a fresh buffer
        return body, (shape(plan.cap),)
    if op == "slab_unpack":   # the receiver's output from its buffer
        return body, (shape(plan.buf_rows),)
    return body, (shape(plan.buf_rows), shape(payload))


@pytest.mark.parametrize("F", sorted(WIDTHS.values()) + list(PADDED_WIDTHS))
@pytest.mark.parametrize("op", ["slab_extract", "slab_merge", "slab_step",
                                "slab_merge_add", "slab_step_reduce",
                                "slab_fill", "slab_unpack"])
def test_slab_kernel_compiles_at_moe_width(op, F, one_chip, pallas):
    """Each slab kernel at the buffer, payloads and offsets of the first
    transfer of the mixtral dispatch plan whose send and receive rows
    are both unaligned, in the row view of the width the executor holds
    the rows at."""
    body, args = _slab_program(op, F, one_chip)
    compiled = jax.jit(body).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled)


@pytest.mark.parametrize("name", KERNEL_NAMES)
def test_kernel_instruction_carries_its_name(name, one_chip, pallas):
    """Every kernel is an HLO custom-call named after it, which is the
    name a device trace gives it."""
    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    if name == "ragged_gather":
        body = functools.partial(ops.ragged_gather, interpret=False)
        args = (arg((512, 128), jnp.float32), arg((256,), jnp.int32))
    elif name == "ragged_scatter":
        body = functools.partial(ops.ragged_scatter, n_out=512,
                                 interpret=False)
        args = (arg((256, 128), jnp.float32), arg((256,), jnp.int32))
    else:
        body, args = _slab_program(name, WIDTHS["mixtral-8x7b"], one_chip)
    hlo = jax.jit(body).lower(*args).compile().as_text()
    assert re.search(rf"%{name}(\.\d+)* = [^\n]* custom-call\(", hlo), name


EXECUTORS = ["gatherv", "scatterv", "allgatherv", "alltoallv",
             "reduce_scatterv", "allreducev"]
# the op that zeroes each fill-built buffer; scatterv's buffer is its input
ZEROED = {"gatherv": "broadcast", "allgatherv": "broadcast",
          "reduce_scatterv": "pad", "allreducev": "pad"}


def _compile_executor(op: str, model: str, F: int, mesh4) -> str:
    """The whole SPMD executor of ``op`` for ``model``'s dispatch plan at
    (rows, F) bf16 on the four described chips, compiled: the Pallas
    kernels are in the program, no whole-buffer relayout but that of an
    input or output which is the whole buffer, whole-buffer zeros in
    every executor that builds its buffer by ``_fill`` but alltoallv, one
    collective-permute per plan step at least, and it fits one chip's
    HBM.  alltoallv's output is built by one ``slab_unpack`` kernel: no
    output-sized zeros, pad or ``dynamic-update-slice``.  Returns its
    HLO."""
    plan = _plan(model, op)
    rows = (plan.buf_rows if op == "scatterv" else
            plan.in_rows if op in ("reduce_scatterv", "allreducev") else
            plan.cap)
    shard = getattr(jc, op + "_shard")
    fn = jax.jit(jax.shard_map(lambda xl: shard(xl, plan, "x"), mesh=mesh4,
                               in_specs=P("x"), out_specs=P("x"),
                               check_vma=False))
    x = jax.ShapeDtypeStruct((4 * rows, F), jnp.bfloat16,
                             sharding=NamedSharding(mesh4, P("x")))
    compiled = fn.lower(x).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    # the capacity buffer lives in the kernels' row view from fill to
    # unpack: only an input or an output that IS the whole buffer is relaid
    moved = _whole_buffer_ops(hlo, plan.buf_rows * F)
    assert len(moved) <= (0 if op in ("alltoallv", "reduce_scatterv")
                          else 1), moved
    # alltoallv's buffer is not zeroed (``slab_fill``); the executors whose
    # unwritten rows are output or summed into keep their zeros, which
    # XLA folds into a pad where the input sits at row 0
    zeros = _whole_buffer_ops(hlo, plan.buf_rows * F, ("broadcast", "pad"))
    if op == "alltoallv":
        assert not zeros, zeros
        unpacks = re.findall(r"%slab_unpack(?:\.\d+)* = [^\n]* custom-call\(",
                             hlo)
        assert len(unpacks) == 1, len(unpacks)
        # the kernel's rows reach the output in one relayout, under a row
        # mask that XLA fuses into it: no zeroed output, no block written
        # into one
        filled = _whole_buffer_ops(hlo, plan.out_rows * F,
                                   ("dynamic-update-slice", "pad",
                                    "broadcast"), fused=False)
        assert not filled, filled
    elif op in ZEROED:
        assert any(f"({ZEROED[op]})" in z for z in zeros), zeros
    permutes = len(re.findall(r" collective-permute(?:-start)?\(", hlo))
    assert permutes >= len(plan.steps), permutes
    _fits(compiled)
    return hlo


@pytest.mark.parametrize("model", sorted(WIDTHS))
@pytest.mark.parametrize("op", EXECUTORS)
def test_executor_compiles_on_2x2_mesh(op, model, mesh4, pallas):
    """The executor at the model's width (``_compile_executor``); its rows
    are whole tiles, so the program pads and slices no lanes."""
    hlo = _compile_executor(op, model, WIDTHS[model], mesh4)
    assert jc.LANE_PAD not in hlo


@pytest.mark.parametrize("op", EXECUTORS)
def test_executor_compiles_lane_padded_on_2x2_mesh(op, mesh4, pallas):
    """The executor at Nemotron-3-Nano-30B-A3B's 2688-wide bf16 rows, which
    the capacity buffer holds at 3072 lanes, over the deepseek-moe-16b
    dispatch plan (top-6, as Nemotron routes): it compiles, fits, and
    pads and slices lanes under ``ragged.lane_pad``."""
    hlo = _compile_executor(op, "deepseek-moe-16b", 2688, mesh4)
    assert jc.LANE_PAD in hlo
