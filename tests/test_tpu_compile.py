"""The main path's kernels compile for a v5e chip at minitron-4b widths.

Interpret mode (every other kernel test) cannot see what the TPU
compiler refuses: blocks that break the (8, 128) tiling, or more VMEM
than a kernel may use.  These tests compile the kernels for a
described (not attached) ``v5e:2x2`` topology, one chip of it, at the
geometries the serve path runs: minitron-4b's factored ``ffn_up``,
``ffn_down`` and ``unembed`` with the ranks ``decompose_model`` picks
at compression 2 (aligned), for a decode batch (M=8) and a prefill
chunk (M=256), and the decode-attention kernels at KH=8, G=3, D=128
(plus the MLA latent kernel at deepseek-v2's 128 heads, latent 512,
rope 64).

Each test first asks :func:`repro.kernels.ops.kernel_fits`: a geometry
it admits must compile, which is what the shared ``vmem_limit_bytes``
buys.  The topology is described inside a fixture only (never at
import), so every xdist worker collects the same tests and only the
worker that runs this file loads the TPU library.

The last tests compile whole serve programs, the runner's decode step
and chunk step at 4 layers, and read the compiled text: the layer scan
must write its carried KV stack in place and read each layer once,
with no whole-layer copy and no write-back of a layer into the stack.
"""
import dataclasses
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import registry
from repro.core import rank_selection, surgery
from repro.kernels import ops, tpu
from repro.models import blocks
from repro.models.api import get_model
from repro.serve.runner import ModelRunner

# (C, R, S) of minitron-4b's factored linears at compression 2, aligned
LOWRANK_GEOMETRIES = {
    "ffn_up": (3072, 1152, 9216),
    "ffn_down": (9216, 1152, 3072),
    "unembed": (3072, 1408, 256000),
}
B, S_MAX, KH, G, D, BLOCK = 8, 1024, 8, 3, 128, 16


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "can't"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # compiles for a described chip are written to the persistent cache
    # but can never be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture
def compiled(one_chip, monkeypatch):
    """Compile ``fn`` at ``shapes`` for the described chip, with the
    kernels compiled (the CPU backend would pick interpret mode)."""
    monkeypatch.setattr(tpu, "interpret", lambda: False)

    def go(fn, *shapes):
        args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
                for shape, dtype in shapes]
        return jax.jit(fn).lower(*args).compile()
    return go


def _assert_kernel(exe):
    assert "tpu_custom_call" in exe.as_text()


@pytest.mark.parametrize("m", [8, 256])
@pytest.mark.parametrize("geometry", sorted(LOWRANK_GEOMETRIES))
def test_lowrank_matmul_compiles(compiled, geometry, m):
    c, r, s = LOWRANK_GEOMETRIES[geometry]
    assert ops.kernel_fits("lowrank", m, c=c, r=r, s=s)
    bf = jnp.bfloat16
    exe = compiled(lambda x, w0, w1: ops.lowrank_matmul(
        x, w0, w1, force_kernel=True),
        ((m, c), bf), ((c, r), bf), ((r, s), bf))
    _assert_kernel(exe)


@pytest.mark.parametrize("m", [8, 256])
@pytest.mark.parametrize("geometry", sorted(LOWRANK_GEOMETRIES))
def test_lowrank_matmul_q_compiles(compiled, geometry, m):
    c, r, s = LOWRANK_GEOMETRIES[geometry]
    assert ops.kernel_fits("lowrank_q", m, c=c, r=r, s=s)
    i8, f32 = jnp.int8, jnp.float32
    exe = compiled(lambda x, w0, s0, w1, s1: ops.lowrank_matmul_q(
        x, w0, s0, w1, s1, force_kernel=True),
        ((m, c), jnp.bfloat16), ((c, r), i8), ((1, r), f32),
        ((r, s), i8), ((1, s), f32))
    _assert_kernel(exe)


def test_decode_attention_q_compiles(compiled):
    assert ops.kernel_fits("decode_attn_q", B, c=D, s=S_MAX, r=G, kh=KH)
    i8, f32 = jnp.int8, jnp.float32
    kv, sc = ((B, S_MAX, KH, D), i8), ((B, KH, D), f32)
    exe = compiled(lambda q, k, ks, v, vs, pos: ops.decode_attention_q(
        q, k, ks, v, vs, pos, force_kernel=True),
        ((B, 1, KH * G, D), jnp.bfloat16), kv, sc, kv, sc,
        ((B,), jnp.int32))
    _assert_kernel(exe)


@pytest.mark.parametrize("quantized", [False, True],
                         ids=["paged", "paged_q"])
def test_decode_attention_paged_compiles(compiled, quantized):
    assert ops.kernel_fits("decode_attn_paged", B, c=D, s=BLOCK, r=G,
                           kh=KH, bn=BLOCK)
    nblk = S_MAX // BLOCK
    blocks = B * nblk + 1                 # + the reserved dummy block
    q = ((B, 1, KH * G, D), jnp.bfloat16)
    tables = ((B, nblk), jnp.int32)
    pos = ((B,), jnp.int32)
    if quantized:
        kv = ((blocks, BLOCK, KH, D), jnp.int8)
        sc = ((blocks, KH, D), jnp.float32)
        exe = compiled(
            lambda q, k, ks, v, vs, bt, p: ops.decode_attention_paged_q(
                q, k, ks, v, vs, bt, p, force_kernel=True),
            q, kv, sc, kv, sc, tables, pos)
    else:
        kv = ((blocks, BLOCK, KH, D), jnp.bfloat16)
        exe = compiled(
            lambda q, k, v, bt, p: ops.decode_attention_paged(
                q, k, v, bt, p, force_kernel=True),
            q, kv, kv, tables, pos)
    _assert_kernel(exe)


def test_decode_attention_latent_q_compiles(compiled):
    h, lora, rope = 128, 512, 64          # deepseek-v2's MLA geometry
    assert ops.kernel_fits("decode_latent_q", B, c=lora, s=S_MAX, r=h,
                           r1=rope)
    bf, i8, f32 = jnp.bfloat16, jnp.int8, jnp.float32
    exe = compiled(
        lambda ql, qr, c, cs, k, ks, p: ops.decode_attention_latent_q(
            ql, qr, c, cs, k, ks, p, scale=0.07, force_kernel=True),
        ((B, 1, h, lora), bf), ((B, 1, h, rope), bf),
        ((B, S_MAX, lora), i8), ((B, lora), f32),
        ((B, S_MAX, rope), i8), ((B, rope), f32), ((B,), jnp.int32))
    _assert_kernel(exe)


# -- the serve programs' layer scan ------------------------------------------

_INSTR = re.compile(r"^(?:ROOT )?%(\S+) = \w+\[([\d,]*)\](\{[^}]*\})? "
                    r"([\w-]+)\((.*)")


def _computations(text):
    """``({name: [instruction lines]}, entry name)`` of an HLO module."""
    comps, entry, name = {}, None, None
    for line in text.splitlines():
        head = re.match(r"(ENTRY )?%(\S+) \(.*\{$", line)
        if head:
            name = head.group(2)
            comps[name] = []
            entry = name if head.group(1) else entry
        elif line.strip() == "}":
            name = None
        elif name:
            comps[name].append(line.strip())
    return comps, entry


def _instrs(lines):
    """``(name, dims, layout, opcode, operands...)`` of array results."""
    for line in lines:
        m = _INSTR.match(line)
        if m:
            name, dims, layout, op, rest = m.groups()
            yield (name, tuple(int(d) for d in dims.split(",") if d),
                   layout or "", op, rest)


def _reached(comps, name, seen):
    seen.add(name)
    for line in comps[name]:
        for c in re.findall(r"(?:calls|to_apply|body|condition)=%([\w.-]+)",
                            line):
            if c not in seen:
                _reached(comps, c, seen)
    return seen


def _squeeze(dims):
    return tuple(d for d in dims if d != 1)


def _factored(shapes):
    """The decomposed tree's shapes: every linear the serve path factors
    becomes ``w0 (C, R)``, ``w1 (R, S)`` at the aligned rank."""
    if surgery._is_linear_node(shapes):
        w = shapes["w"]
        c, s = w.shape[-2:]
        r = rank_selection.select_rank(c, s, compression=2.0,
                                       mode="aligned", align=128)
        if min(c, s) < 256 or r == rank_selection.ORG:
            return shapes
        lead = w.shape[:-2]
        return {"w0": jax.ShapeDtypeStruct((*lead, c, r), w.dtype),
                "w1": jax.ShapeDtypeStruct((*lead, r, s), w.dtype)}
    if isinstance(shapes, dict):
        return {k: _factored(v) if k != "embed" else v
                for k, v in shapes.items()}
    return shapes


#: arch, step, pool (slots, max_seq): chat's decode pool and longdoc's
#: chunked-prefill staging cache, both cut to 4 layers
SERVE_PROGRAMS = {
    "minitron-4b-decode": ("minitron-4b", "decode", 24, 1536),
    "mistral-nemo-12b-prefill_chunk": ("mistral-nemo-12b", "prefill_chunk",
                                       1, 4096),
}


@pytest.mark.parametrize("program", sorted(SERVE_PROGRAMS))
def test_layer_scan_writes_the_kv_stack_in_place(one_chip, monkeypatch,
                                                 program):
    arch, step, slots, max_seq = SERVE_PROGRAMS[program]
    monkeypatch.setattr(tpu, "interpret", lambda: False)
    layers = 4
    cfg = dataclasses.replace(registry.get(arch).full, num_layers=layers)
    model = get_model(cfg)
    params = _factored(jax.eval_shape(
        lambda k: model.init(k)[0], jax.random.PRNGKey(0)))
    runner = ModelRunner(model, params, blocks.BlockOpts(use_pallas=True),
                         max_seq=max_seq)
    put = functools.partial(jax.tree.map, lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=one_chip))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32,
                                              sharding=one_chip)
    cache = model.cache_spec(slots, max_seq)
    if step == "decode":
        exe = runner.jit_decode.lower(put(params), i32(slots, 1), i32(slots),
                                      put(cache)).compile()
    else:
        exe = runner.jit_prefill_chunk.lower(
            put(params), {"tokens": i32(1, 64)}, put(cache), i32(),
            i32()).compile()

    comps, entry = _computations(exe.as_text())
    layer = (slots, max_seq, cfg.num_kv_heads, cfg.resolved_head_dim)
    stack = (layers, *layer)
    leaves = len(jax.tree.leaves(cache))
    bodies = [b for line in comps[entry]
              for b in re.findall(r"body=%([\w.-]+)", line)]
    assert bodies, "no layer scan in the compiled program"
    for body in bodies:
        # one layer's K/V is only ever staged on core (memory space 1)
        # as attention's read, never copied in HBM
        copies = [name for name, dims, layout, op, _ in _instrs(comps[body])
                  if _squeeze(dims) == _squeeze(layer) and "S(1)" not in layout]
        assert not copies, f"layer-sized K/V in HBM in the scan: {copies}"
        # new rows go into the stack; no whole layer is written back
        for comp in _reached(comps, body, set()):
            dims_of = {n: d for n, d, *_ in _instrs(comps[comp])}
            for name, dims, _, op, rest in _instrs(comps[comp]):
                if op != "dynamic-update-slice":
                    continue
                update = dims_of.get(re.findall(r"%([\w.-]+)", rest)[1], ())
                assert _squeeze(update) != _squeeze(layer), (
                    f"{name} writes a whole layer back into {dims}")
    pool_copies = [name for name, dims, _, op, _ in _instrs(comps[entry])
                   if op in ("copy", "copy-start")
                   and _squeeze(dims) == _squeeze(stack)]
    assert len(pool_copies) <= leaves, pool_copies
