"""Compile the main path's Mosaic kernels for a described TPU v5e.

Nothing runs: ``jax.experimental.topologies`` describes a v5e:2x2 host that
is not attached, and each kernel is lowered and compiled for one of its
chips at the payload widths a round hands it (flat-packed deltas are
``(..., R, 256)`` f32 buffers; 4096 rows is a 1M-entry pod partial). The
TPU compiler refuses here what the chip would refuse: unaligned blocks, or
more VMEM than a kernel may use (the fixed 256-row block did, from 32
groups on). Interpret-mode tests (``test_kernels.py``,
``test_fused_reduce.py``) cannot see either.

Each kernel's custom call carries the kernel's ``name`` in its ``op_name``
(the scope ``<name>/pallas_call``), which is how a profile finds it: the
custom call target ``tpu_custom_call`` is shared by every Mosaic kernel.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops

ROWS, LANES = 4096, 256
OP_NAME = re.compile(r'op_name="([^"]*)"')


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - no TPU compiler to describe it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around these.
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return jax.sharding.SingleDeviceSharding(topo.devices[0])


def _compile_hlo(fn, sharding, *shapes):
    args = [
        jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
        for shape, dtype in shapes
    ]
    return jax.jit(fn).lower(*args).compile().as_text()


def _assert_named_kernel(hlo, name):
    """The HLO holds the Mosaic kernel, and each of its custom calls is
    named ``name`` (``vmap(name)`` under a vmap)."""
    calls = [line for line in hlo.splitlines() if "tpu_custom_call" in line]
    assert calls
    for line in calls:
        (op_name,) = OP_NAME.findall(line)
        assert op_name.split("/")[-2] in (name, f"vmap({name})"), op_name


@pytest.mark.parametrize("groups", [8, 64])
def test_reduce_compress(one_chip, groups):
    hlo = _compile_hlo(
        lambda x: ops.reduce_compress(x, backend="pallas", interpret=False),
        one_chip, ((groups, ROWS, LANES), jnp.float32),
    )
    _assert_named_kernel(hlo, "reduce_compress")


@pytest.mark.parametrize("groups", [8, 64])
def test_reduce_compress_roundtrip(one_chip, groups):
    hlo = _compile_hlo(
        lambda x: ops.reduce_compress_roundtrip(
            x, backend="pallas", interpret=False
        ),
        one_chip, ((groups, ROWS, LANES), jnp.float32),
    )
    _assert_named_kernel(hlo, "reduce_compress_roundtrip")


@pytest.mark.parametrize("pods", [8, 64])
def test_dequant_accumulate(one_chip, pods):
    hlo = _compile_hlo(
        lambda q, s: ops.dequant_accumulate(q, s, backend="pallas",
                                             interpret=False),
        one_chip,
        ((pods, ROWS, LANES), jnp.int8),
        ((pods, ROWS), jnp.float32),
    )
    _assert_named_kernel(hlo, "dequant_accumulate")


# The cross-pod leg's pair keeps its scales lane-dense, so a block holds a
# multiple of 128 rows: at a row count that is no multiple of 128 the last
# block is partial.
@pytest.mark.parametrize("groups", [1, 8])
def test_reduce_compress_partial_block(one_chip, groups):
    hlo = _compile_hlo(
        lambda x: ops.reduce_compress(x, backend="pallas"),
        one_chip, ((groups, ROWS + 100, LANES), jnp.float32),
    )
    _assert_named_kernel(hlo, "reduce_compress")


@pytest.mark.parametrize("pods", [2, 8, 64])
def test_dequant_accumulate_partial_block(one_chip, pods):
    hlo = _compile_hlo(
        lambda q, s: ops.dequant_accumulate(q, s, backend="pallas"),
        one_chip,
        ((pods, ROWS + 100, LANES), jnp.int8),
        ((pods, ROWS + 100), jnp.float32),
    )
    _assert_named_kernel(hlo, "dequant_accumulate")


def test_quantize(one_chip):
    hlo = _compile_hlo(
        lambda x: ops.quantize(x, interpret=False),
        one_chip, ((4096, 1024), jnp.float32),
    )
    _assert_named_kernel(hlo, "quantize")


def test_dequantize(one_chip):
    hlo = _compile_hlo(
        lambda q, s: ops.dequantize(q, s, interpret=False),
        one_chip, ((4096, 1024), jnp.int8), ((4096, 1), jnp.float32),
    )
    _assert_named_kernel(hlo, "dequantize")


# ---------------------------------------------------------------------------
# the flat local-SGD round's reduce and server step, outside the client loop
# ---------------------------------------------------------------------------

_ARRAY = re.compile(r"\b(bf16|f32|s32|u32|s8|u8|pred)\[([\d,]*)\]")
_ITEMSIZE = {"bf16": 2, "f32": 4, "s32": 4, "u32": 4, "s8": 1, "u8": 1,
             "pred": 1}
# Ops that move no data of their own, or only move it into place.
_NO_BYTES = ("get-tuple-element", "bitcast", "tuple")
_PASS = ("copy", "copy-start", "copy-done", "bitcast", "slice-start",
         "slice-done", "get-tuple-element", "custom-call")
_AGGREGATE = ("drjax.broadcast", "drjax.reduce_mean", "client_delta",
              "server_update")


def _bytes(shape: str) -> int:
    """Bytes of every array in an HLO shape (a tuple sums its elements)."""
    total = 0
    for dtype, dims in _ARRAY.findall(shape):
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        total += n * _ITEMSIZE[dtype]
    return total


def _closing(text: str, start: int) -> int:
    depth = 0
    for j in range(start, len(text)):
        if text[j] in "({[":
            depth += 1
        elif text[j] in ")}]":
            depth -= 1
            if depth == 0:
                return j
    raise ValueError(text)


def _entry_ops(hlo: str) -> dict:
    """The compiled program's top-level instructions (those of its entry
    computation) by name: shape, opcode, operands, op_name and the line."""
    body = hlo[hlo.index("\nENTRY"):]
    ops = {}
    for line in body[body.index("{\n") + 2:].splitlines():
        m = re.match(r"\s*(ROOT )?%(\S+) = ", line)
        if not m:
            continue
        rest = line[m.end():]
        if rest.startswith("("):
            end = _closing(rest, 0) + 1
            shape, rest = rest[:end], rest[end + 1:]
        else:
            shape, rest = rest.split(" ", 1)
        opcode = rest[:rest.index("(")]
        args = rest[len(opcode):_closing(rest, len(opcode)) + 1]
        (op_name,) = OP_NAME.findall(rest) or ("",)
        ops[m.group(2)] = dict(
            shape=shape, opcode=opcode, operands=re.findall(r"%([\w.\-]+)", args),
            op_name=op_name, line=line, root=bool(m.group(1)))
    return ops


def _compile_round(one_chip, algorithm):
    """The flat local-SGD round of ``launch.train`` at reduced widths, bf16
    parameters, compiled for one v5e: (HLO text, parameters, server state)."""
    import dataclasses

    from repro.launch import train as train_lib
    from repro.models import registry

    cfg = registry.get_config("lm_1b").reduced(
        dtype="bfloat16", d_model=256, num_heads=2, num_kv_heads=2,
        head_dim=128, d_ff=512, vocab_size=512)
    cfg = dataclasses.replace(cfg, remat="full")
    args = train_lib.parse_args(
        ["--arch", "lm_1b", "--algorithm", algorithm, "--cohort", "2",
         "--local-steps", "2", "--batch", "2", "--seq", "128"])
    step, server_opt = train_lib.build_round_fn(cfg, args)

    def placed(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=one_chip), tree)

    params = jax.eval_shape(
        lambda: registry.init_params(jax.random.PRNGKey(0), cfg))
    state = jax.eval_shape(server_opt.init, params)
    batch = {k: jax.ShapeDtypeStruct((2, 2, 2, 128), jnp.int32,
                                     sharding=one_chip)
             for k in ("tokens", "labels")}
    hlo = step.lower(placed(params), placed(state), batch).compile().as_text()
    return hlo, params, state


@pytest.fixture(scope="module", params=["local_sgd", "fedavg", "diloco"])
def flat_round(one_chip, request):
    return _compile_round(one_chip, request.param)


def _moves(op) -> bool:
    """Copies, async slices and their reassembly: data moved into place."""
    return op["opcode"] in _PASS and (
        op["opcode"] != "custom-call" or "ConcatBitcast" in op["line"])


def test_flat_round_reduces_and_steps_in_one_pass(flat_round):
    """Outside the client loop, each parameter leaf's mean over the clients
    and its server step are one fusion, which reads the clients' new
    parameters and the global (and the server's f32 state) as they are:
    no f32 copy of the global, and no f32 mean, is written out between
    them. The fusion is named ``server_update``, the aggregate leg."""
    hlo, params, _ = flat_round
    ops = _entry_ops(hlo)
    (root,) = [op for op in ops.values() if op["root"]]
    whiles = {name for name, op in ops.items() if op["opcode"] == "while"}
    number = {int(re.search(r"parameter\((\d+)\)", op["line"]).group(1)): name
              for name, op in ops.items() if op["opcode"] == "parameter"}

    def sources(op):
        """What a fusion reads, through copies and async slices: entry
        parameters, the client loop's results ("clients"), or other ops."""
        out, todo = set(), list(op["operands"])
        while todo:
            name = todo.pop()
            cur = ops[name]
            if cur["opcode"] == "get-tuple-element" and cur["operands"][0] in whiles:
                out.add("clients")
            elif cur["opcode"] == "parameter" or not _moves(cur):
                out.add(name)
            else:
                todo.extend(cur["operands"])
        return out

    leaves = jax.tree_util.tree_leaves(params)
    for i, leaf in enumerate(leaves):
        if leaf.ndim < 2:
            continue  # a vector leaf of a few hundred values may split
        op = ops[root["operands"][i]]
        while op["opcode"] in ("copy", "copy-start", "copy-done", "bitcast",
                               "get-tuple-element"):  # or a multi-output fusion
            op = ops[op["operands"][0]]
        assert op["opcode"] == "fusion", (i, op["line"][:200])
        assert "server_update" in op["op_name"], op["op_name"]
        read = sources(op)
        assert {number[i], "clients"} <= read, (i, read)
        assert all(s == "clients" or ops[s]["opcode"] == "parameter"
                   for s in read), (i, [ops[s]["line"][:120] for s in read
                                        if s != "clients"])


def test_flat_round_aggregate_bytes(flat_round):
    """The ops scoped ``drjax.broadcast``, ``drjax.reduce_mean``,
    ``client_delta`` and ``server_update`` move at most the broadcast's
    bytes plus 8 B a parameter (read two clients' bf16 parameters and the
    global, write the global), and 8 B for each value of f32 server state
    (read once, written once), with 5 % to spare."""
    hlo, params, state = flat_round
    ops = _entry_ops(hlo)
    moved = {scope: 0 for scope in _AGGREGATE}
    for op in ops.values():
        scope = next((s for s in _AGGREGATE if s in op["op_name"]), None)
        if scope is None or op["opcode"] in _NO_BYTES:
            continue
        moved[scope] += _bytes(op["shape"]) + sum(
            _bytes(ops[x]["shape"]) for x in op["operands"])
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    n_state = sum(x.size for x in jax.tree_util.tree_leaves(state)
                  if x.dtype == jnp.float32 and x.ndim)
    rest = sum(v for s, v in moved.items() if s != "drjax.broadcast")
    assert moved["drjax.broadcast"] > 0
    assert rest <= 1.05 * (8 * n_params + 8 * n_state), (moved, n_params)


# Opcodes of the instructions that move data between devices.
_COLLECTIVE = re.compile(
    r"= [^=]*? (all-reduce|all-gather|reduce-scatter|collective-permute|"
    r"all-to-all)(-start|-done)?\(")


_GROUPS = re.compile(r"replica_groups=(\{\{[\d,{}]*\}\}|"
                     r"\[[\d,]+\]<=\[[\d,]+\](?:T\([\d,]+\))?)")


def _kernel_names(hlo: str) -> dict:
    """Each Mosaic kernel's name (``vmap(name)`` counts as ``name``) and
    the ``op_name`` of each of its custom calls."""
    out = {}
    for line in hlo.splitlines():
        if "tpu_custom_call" in line:
            (op_name,) = OP_NAME.findall(line)
            name = op_name.split("/")[-2]
            name = name[5:-1] if name.startswith("vmap(") else name
            out.setdefault(name, []).append(op_name)
    return out


def _replica_groups(text: str) -> frozenset:
    """HLO ``replica_groups``, in either the explicit or the iota form, as
    a set of groups of device ids."""
    if text.startswith("{"):
        return frozenset(frozenset(int(d) for d in g.split(","))
                         for g in re.findall(r"\{([\d,]+)\}", text))
    shape, dims, perm = re.match(
        r"\[([\d,]+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?", text).groups()
    ids = np.arange(np.prod([int(d) for d in dims.split(",")]))
    ids = ids.reshape([int(d) for d in dims.split(",")])
    if perm:
        ids = ids.transpose([int(d) for d in perm.split(",")])
    ids = ids.reshape([int(d) for d in shape.split(",")])
    return frozenset(frozenset(row) for row in ids.tolist())


def _mesh_groups(mesh, axis: str) -> frozenset:
    """The device groups (ids in the mesh's order) that ``axis`` joins."""
    ids = np.moveaxis(np.arange(mesh.devices.size).reshape(mesh.devices.shape),
                      mesh.axis_names.index(axis), -1)
    return frozenset(frozenset(row) for row in
                     ids.reshape(-1, ids.shape[-1]).tolist())


# Bytes one member of a group of n sends, per byte of the op's result.
_SENT = {"all-gather": lambda n: (n - 1) / n,
         "all-to-all": lambda n: (n - 1) / n,
         "all-reduce": lambda n: 2 * (n - 1) / n,
         "reduce-scatter": lambda n: n - 1,
         "collective-permute": lambda n: 1}


def _collectives(hlo: str) -> list:
    """The compiled program's collectives, one entry for each array of a
    (combined) result: opcode, dtype and dims, replica groups, and the
    bytes each member of a group sends."""
    out = []
    for line in hlo.splitlines():
        m = _COLLECTIVE.search(line)
        if not m or m.group(2) == "-done":
            continue
        shape = line.split(" = ", 1)[1].split(f" {m.group(1)}", 1)[0]
        arrays = _ARRAY.findall(shape)
        if m.group(2):  # an async start's result is the last array
            arrays = arrays[-1:]
        groups = _replica_groups(_GROUPS.search(line).group(1))
        n = len(next(iter(groups)))
        for dtype, dims in arrays:
            dims = [int(d) for d in filter(None, dims.split(","))]
            size = int(np.prod(dims)) * _ITEMSIZE[dtype]
            out.append(dict(op=m.group(1), dtype=dtype, dims=dims,
                            groups=groups, sent=_SENT[m.group(1)](n) * size))
    return out


def test_nested_round_runs_the_kernel_on_every_chip(topo, monkeypatch):
    """``launch.train --pods 2 --compression int8`` on a (pod 2, data 2)
    mesh of the four described chips, at reduced widths: the one program
    every chip runs holds the Mosaic ``reduce_compress_roundtrip`` call,
    and it and every collective bind under a ``drjax.`` scope, where the
    benchmark's readers find them. Each client's delta rounds its new
    parameters to bf16 with an explicit ``reduce-precision``: without it
    the TPU compiler keeps them at f32 (excess precision), and the pod
    partials are no longer those of bf16 clients."""
    from repro.launch import train as train_lib
    from repro.models import registry

    # The kernel dispatch asks for the backend, which here is the CPU.
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    jax.clear_caches()
    cfg = registry.get_config("lm_350m").reduced(
        dtype="bfloat16", d_model=256, num_heads=2, num_kv_heads=2,
        head_dim=128, d_ff=512, vocab_size=512)
    args = train_lib.parse_args(
        ["--arch", "lm_350m", "--pods", "2", "--cohort", "8",
         "--local-steps", "1", "--batch", "2", "--seq", "128",
         "--compression", "int8"])
    devices = list(topo.devices)
    mesh = train_lib.round_layout(args, devices).mesh
    step, server_opt = train_lib.build_round_fn(cfg, args, mesh)
    replicated = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    sharded = jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec(*mesh.axis_names))

    def placed(tree, sharding):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=sharding), tree)

    params = jax.eval_shape(
        lambda: registry.init_params(jax.random.PRNGKey(0), cfg))
    state = jax.eval_shape(server_opt.init, params)
    batch = {k: jax.ShapeDtypeStruct((2, 4, 1, 2, 128), jnp.int32,
                                     sharding=sharded)
             for k in ("tokens", "labels")}
    hlo = step.lower(placed(params, replicated), placed(state, replicated),
                     batch).compile().as_text()
    jax.clear_caches()
    kernels = _kernel_names(hlo)
    assert kernels["reduce_compress_roundtrip"], kernels
    lines = hlo.splitlines()
    kernel = [line for line in lines if "tpu_custom_call" in line]
    collectives = [line for line in lines if _COLLECTIVE.search(line)]
    assert len(collectives) >= 2
    for line in kernel + collectives:
        (op_name,) = OP_NAME.findall(line)
        assert "drjax." in op_name, line
    rounding = [line for line in lines if " reduce-precision(" in line
                and "client_delta" in line]
    assert rounding and all("mantissa_bits=7" in line for line in rounding)
    # The cross-pod leg: the pod partials leave each pod as int8 values and
    # lane-dense f32 scales, requantized and dequantized by the kernel pair
    # under the outer reduce's scope; only the intra-pod sum moves f32 rows.
    assert set(kernels) == {"reduce_compress_roundtrip", "reduce_compress",
                            "dequant_accumulate"}, kernels
    for name in ("reduce_compress", "dequant_accumulate"):
        assert all("drjax.reduce_mean[pods]" in op for op in kernels[name])
    pods, intra = _mesh_groups(mesh, "pod"), _mesh_groups(mesh, "data")
    ops_ = _collectives(hlo)
    (partial,) = [c for c in ops_ if c["groups"] == intra
                  and c["dtype"] == "f32" and c["dims"][-1:] == [256]]
    rows = partial["dims"][-2]
    across = [c for c in ops_ if c["groups"] == pods]
    assert not [c for c in across
                if c["dtype"] == "f32" and c["dims"][-1:] == [256]], across
    assert [c for c in across if c["dtype"] == "s8"
            and c["dims"][-2:] == [rows, 256]], across
    assert [c for c in across if c["dtype"] == "f32"
            and c["dims"][-1:] == [rows]], across
    sent = sum(c["sent"] for c in across)
    assert sent <= 0.26 * rows * 256 * 4, (sent, rows)
    # Each chip sums its own two clients before the pack: the pack's
    # concatenate holds one group, and no f32 buffer of the packed rows
    # holds more (as one that packed both clients, then reduced them, would).
    packs = [op for op in _entry_ops(hlo).values()
             if op["opcode"] == "concatenate"
             and "drjax.reduce_compress[clients]" in op["op_name"]]
    assert [_ARRAY.findall(op["shape"]) for op in packs] == [
        [("f32", f"1,1,{rows},256")]], [op["line"][:200] for op in packs]
    grouped = {dims for dtype, dims in _ARRAY.findall(hlo)
               if dtype == "f32" and dims.endswith(f",{rows},256")
               and np.prod([int(d) for d in dims.split(",")[:-2]]) > 1}
    assert not grouped, grouped
    # The collectives are those a pack of every client would need: the
    # intra-pod partial and the loss inside the pods, the int8 values, the
    # scales and the loss across them.
    assert sorted((c["op"], c["dtype"], c["dims"], c["groups"] == pods)
                  for c in ops_) == sorted([
        ("all-reduce", "f32", [1, rows, 256], False),
        ("all-reduce", "f32", [1], False),
        ("all-to-all", "s8", [2, rows, 256], True),
        ("all-to-all", "f32", [2, 1, rows], True),
        ("all-reduce", "f32", [], True)]), ops_


def test_nested_int8_reduce_sends_what_the_analyzer_counts(topo, monkeypatch):
    """``plan.comm_cost()`` counts the nested int8 reduce's cross-pod (DCN)
    stage at one byte a value and one f32 scale a row of 256, each pod's
    partial once. Compiled for a (pod 2, data 2) mesh of the described
    chips, every device of a pod holds the pod's partial and sends it: the
    two cross-pod groups (one a data index) send twice that together, to
    5 %."""
    from jax.sharding import NamedSharding, PartitionSpec

    from repro import compat
    from repro import core as drjax
    from repro.compression import int8_roundtrip

    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    jax.clear_caches()
    mesh = compat.make_mesh((2, 2), ("pod", "data"), devices=topo.devices)
    sizes = {"pods": 2, "clients": 4}

    @drjax.program(placements=sizes, mesh=mesh,
                   partition_axes={"pods": "pod", "clients": "data"})
    def f(xs):
        return drjax.hierarchical_reduce_mean(xs, compress_fn=int8_roundtrip)

    xs = jax.ShapeDtypeStruct(
        (2, 4, ROWS * LANES), jnp.float32,
        sharding=NamedSharding(mesh, PartitionSpec("pod", "data")))
    dcn = drjax.build_plan(jax.make_jaxpr(f)(xs), sizes).comm_cost().dcn_bytes
    hlo = jax.jit(f).lower(xs).compile().as_text()
    jax.clear_caches()
    pods = _mesh_groups(mesh, "pod")
    sent = sum(len(g) for g in pods) * sum(
        c["sent"] for c in _collectives(hlo) if c["groups"] == pods)
    assert sent == pytest.approx(mesh.shape["data"] * dcn, rel=0.05), (
        sent, dcn)
