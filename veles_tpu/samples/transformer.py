"""Decoder-only transformer LM — the long-context / multi-way-parallel
flagship.

The reference's layer zoo stops at LSTM-era units (SURVEY §5.7: no
attention); this family is the TPU build's beyond-parity capability and
the vehicle for the first-class parallelism requirements: one fused
train step composing

* **DP**  — batch on the ``data`` axis,
* **TP**  — heads / MLP hidden on the ``model`` axis
            (Megatron column→row pairs via GSPMD shardings),
* **SP**  — sequence on the ``seq`` axis with exact
            :func:`~veles_tpu.parallel.ring.ring_attention`
            (flash-style online softmax + ``ppermute`` ring).

Blocks are stacked on a leading layer axis and scanned (`lax.scan`) so
compile time is O(1) in depth; `jax.checkpoint` on the block body
rematerializes activations in backward (HBM-bound regime).
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy
from jax.sharding import NamedSharding, PartitionSpec as P

from veles_tpu.parallel.mesh import replicated
from veles_tpu.parallel.ring import ring_attention

CONFIG = {
    "vocab": 32000, "dim": 1024, "heads": 16, "layers": 12,
    "mlp_ratio": 4, "seq_len": 2048,
}
TINY = {
    "vocab": 64, "dim": 32, "heads": 4, "layers": 2,
    "mlp_ratio": 2, "seq_len": 16,
}


def _shape_table(cfg):
    """The one parameter-layout table: ``name -> (shape, init)`` with
    ``init`` = ("randn", scale) | ("ones",) | ("zeros",).  Both
    :func:`init_params` (allocates) and :func:`param_shapes` (the
    static planner's zero-alloc probe) derive from it, so the layouts
    cannot drift.  Entry order is load-bearing: it is the RNG draw
    order of ``init_params``."""
    d, h, L = cfg["dim"], cfg["heads"], cfg["layers"]
    dh = d // h
    f = cfg["mlp_ratio"] * d
    sq = math.sqrt
    return {
        "embed": ((cfg["vocab"], d), ("randn", 0.02)),
        "pos": ((cfg["seq_len"], d), ("randn", 0.02)),
        "blocks": {
            "ln1_g": ((L, d), ("ones",)),
            "ln1_b": ((L, d), ("zeros",)),
            "wqkv": ((L, d, 3, h, dh), ("randn", 1 / sq(d))),
            "wo": ((L, h, dh, d), ("randn", 1 / sq(d) / sq(2 * L))),
            "ln2_g": ((L, d), ("ones",)),
            "ln2_b": ((L, d), ("zeros",)),
            "w1": ((L, d, f), ("randn", 1 / sq(d))),
            "b1": ((L, f), ("zeros",)),
            "w2": ((L, f, d), ("randn", 1 / sq(f) / sq(2 * L))),
            "b2": ((L, d), ("zeros",)),
        },
        "lnf_g": ((d,), ("ones",)),
        "lnf_b": ((d,), ("zeros",)),
    }


def _build_params(table, make):
    """Walk the shape table in INSERTION order (dict order is the RNG
    draw order — ``jax.tree.map`` would sort keys and change seeds)."""
    out = {}
    for name, entry in table.items():
        out[name] = (_build_params(entry, make)
                     if isinstance(entry, dict) else make(entry))
    return out


def init_params(cfg, seed=0, dtype=numpy.float32):
    """Stacked-block GPT params (leading axis = layer for lax.scan)."""
    rng = numpy.random.default_rng(seed)

    def make(entry):
        shape, init = entry
        if init[0] == "randn":
            return (rng.standard_normal(shape)
                    * init[1]).astype(dtype)
        fn = numpy.ones if init[0] == "ones" else numpy.zeros
        return fn(shape, dtype)

    return _build_params(_shape_table(cfg), make)


def param_shapes(cfg, dtype=numpy.float32):
    """Zero-alloc :class:`jax.ShapeDtypeStruct` twin of
    :func:`init_params` — what ``python -m veles_tpu.analyze --plan``
    prices candidate dp/fsdp/tp/pp plans against (no RNG, no HBM)."""
    dt = numpy.dtype(dtype)
    return _build_params(
        _shape_table(cfg),
        lambda entry: jax.ShapeDtypeStruct(entry[0], dt))


def _layernorm(x, g, b):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + 1e-5) * g + b


def _attend(q, k, v, mesh, seq_axis):
    if mesh is not None and seq_axis and mesh.shape.get(seq_axis, 1) > 1:
        return ring_attention(q, k, v, mesh, causal=True,
                              seq_axis=seq_axis, batch_axis="data",
                              head_axis="model"
                              if mesh.shape.get("model", 1) > 1
                              else None)
    # single-shard sequence: the Pallas flash kernel on TPU (blockwise
    # VJP), XLA-fused fallback elsewhere.  pallas_call has no GSPMD
    # partitioning rule, so under a data/head-sharded mesh the kernel
    # must run per-shard inside shard_map — otherwise XLA all-gathers
    # the activations and every chip does the full attention.
    from veles_tpu.ops.attention import flash_attention
    from veles_tpu.config import root
    if str(root.common.engine.get("kernels", "auto")).lower() == "xla" \
            and mesh is None:
        # the dense XLA reference WITHOUT the blockwise custom_vjp:
        # AD materializes the [B,H,S,S] scores in the backward — the
        # bench ladder's same-run baseline arm
        # (stage_transformer_lm_train) and the escape hatch when the
        # flash kernels are suspect
        from veles_tpu.ops.attention import _mha_jnp
        return _mha_jnp(q, k, v, True)[0]
    if mesh is None:
        return flash_attention(q, k, v, True)
    data = "data" if mesh.shape.get("data", 1) > 1 else None
    model = "model" if mesh.shape.get("model", 1) > 1 else None
    if data is None and model is None:
        return flash_attention(q, k, v, True)
    spec = P(data, None, model, None)
    return jax.shard_map(
        lambda q, k, v: flash_attention(q, k, v, True),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)(q, k, v)


def _block(h, blk, mesh, seq_axis, compute_dtype):
    """One pre-LN transformer block; wqkv [d,3,H,dh], wo [H,dh,d]."""
    B, S, d = h.shape
    # Mixed-precision discipline: every dot accumulates in f32 on the
    # MXU (preferred_element_type) but its RESULT is stored back in
    # compute_dtype immediately — the stored activations are what the
    # backward pass (and the layer scan) keeps live, and f32 residuals
    # at [B,S,4d] were exactly the 5x2 GB buffers that OOM'd the
    # no-remat step on a 16 GB chip (r4 session 4 compile dump).
    # Biases are cast too: a f32 bias add silently promotes the whole
    # activation back to f32.
    # No preferred_element_type=f32 on these dots: the MXU already
    # accumulates bf16 operands in f32 internally, so a f32 OUTPUT
    # (then downcast) buys no precision — but it makes every backward
    # cotangent f32, and the VJP's f32xbf16 matmuls get promoted to
    # the ~3x-slower all-f32 MXU mode.  bf16 outputs keep the whole
    # backward on the fast path.
    x = _layernorm(h, blk["ln1_g"], blk["ln1_b"])
    qkv = jnp.einsum("bsd,dchx->bschx", x.astype(compute_dtype),
                     blk["wqkv"].astype(compute_dtype))
    if mesh is not None and mesh.shape.get("model", 1) > 1:
        qkv = jax.lax.with_sharding_constraint(
            qkv, NamedSharding(
                mesh, P("data", seq_axis, None, "model", None)))
    q, k, v = (qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2])
    att = _attend(q, k, v, mesh, seq_axis)
    proj = jnp.einsum("bshx,hxd->bsd", att.astype(compute_dtype),
                      blk["wo"].astype(compute_dtype))
    h = h + proj.astype(h.dtype)
    x = _layernorm(h, blk["ln2_g"], blk["ln2_b"])
    up = (x.astype(compute_dtype) @ blk["w1"].astype(compute_dtype)
          + blk["b1"].astype(compute_dtype))
    if mesh is not None and mesh.shape.get("model", 1) > 1:
        up = jax.lax.with_sharding_constraint(
            up, NamedSharding(mesh, P("data", seq_axis, "model")))
    act = jax.nn.gelu(up)
    down = (act @ blk["w2"].astype(compute_dtype)
            + blk["b2"].astype(compute_dtype))
    return h + down.astype(h.dtype)


def hidden_fn(params, tokens, cfg=None, mesh=None, seq_axis="seq",
              compute_dtype=jnp.bfloat16, remat=True):
    """tokens [B, S] int32 → final-LN hidden states [B, S, d]."""
    h = params["embed"][tokens] + params["pos"][: tokens.shape[1]]
    if mesh is not None:
        h = jax.lax.with_sharding_constraint(
            h, NamedSharding(mesh, P("data", seq_axis, None)))

    body = functools.partial(_block, mesh=mesh, seq_axis=seq_axis,
                             compute_dtype=compute_dtype)
    if remat:
        body = jax.checkpoint(body)

    def scan_body(h, blk):
        return body(h, blk), None

    h, _ = jax.lax.scan(scan_body, h, params["blocks"])
    return _layernorm(h, params["lnf_g"], params["lnf_b"])


def apply_fn(params, tokens, cfg=None, mesh=None, seq_axis="seq",
             compute_dtype=jnp.bfloat16, remat=True):
    """tokens [B, S] int32 → logits [B, S, V]."""
    h = hidden_fn(params, tokens, cfg, mesh=mesh, seq_axis=seq_axis,
                  compute_dtype=compute_dtype, remat=remat)
    # weight-tied readout (embed^T) keeps the TINY config honest
    # bf16 logits: unlike the qkv dot (which always downcast), this IS
    # a deliberate precision trade — the readout's f32 accumulation is
    # rounded to bf16 (~1e-2-nat per-token CE noise at V=32k), in
    # exchange for bf16 cotangents through the two huge [*,V]x[V,d]
    # backward matmuls (all-f32 promotion is ~3x slower on the MXU).
    # The bf16 lm-head is standard practice at this scale; consumers
    # upcast for the softmax math.
    logits = jnp.einsum("bsd,vd->bsv", h.astype(compute_dtype),
                        params["embed"].astype(compute_dtype))
    return logits


def make_train_step(cfg, mesh=None, seq_axis="seq", lr=3e-4,
                    compute_dtype=jnp.bfloat16, remat=True,
                    ce_chunk=128):
    """(params, opt_state, tokens) → next-token CE loss, SGD+momentum
    update — one XLA program.

    ``ce_chunk``: the cross-entropy never materializes the full
    ``[B, S, V]`` logits (4.2 GB at B=32/S=1024/V=32k in f32); a
    ``lax.scan`` over sequence chunks computes per-chunk logits +
    logsumexp, so CE memory is O(B·chunk·V) and the readout matmul
    stays MXU-sized.  The backward recomputes each chunk's logits —
    the same trade remat already makes for the blocks.  ``ce_chunk=0``
    keeps the plain full-logits path (the equivalence oracle in
    tests/test_parallel.py)."""

    # chunked CE serializes the readout over the scan axis, which a
    # sequence-parallel mesh cannot shard — there the OLD path is the
    # faster one (GSPMD shards the [B,S,V] readout along seq), so
    # chunking applies only when the seq axis is unsharded
    use_chunks = bool(ce_chunk) and (
        mesh is None or mesh.shape.get(seq_axis, 1) <= 1)

    def loss_fn(params, tokens):
        targets = tokens[:, 1:]
        if not use_chunks:
            logits = apply_fn(params, tokens, cfg, mesh=mesh,
                              seq_axis=seq_axis,
                              compute_dtype=compute_dtype, remat=remat)
            logp = jax.nn.log_softmax(
                logits[:, :-1].astype(jnp.float32))
            picked = jnp.take_along_axis(
                logp, targets[..., None], axis=-1)[..., 0]
            return -picked.mean()
        h = hidden_fn(params, tokens, cfg, mesh=mesh, seq_axis=seq_axis,
                      compute_dtype=compute_dtype, remat=remat)
        hs = h[:, :-1]
        batch, n, _d = hs.shape
        chunk = min(ce_chunk, n)
        k = -(-n // chunk)
        pad = k * chunk - n
        hs = jnp.pad(hs, ((0, 0), (0, pad), (0, 0)))
        tg = jnp.pad(targets, ((0, 0), (0, pad)))
        # [k, B, chunk, ...] so the scan carries only the running sum
        hs = hs.reshape(batch, k, chunk, -1).transpose(1, 0, 2, 3)
        tg = tg.reshape(batch, k, chunk).transpose(1, 0, 2)
        valid = (jnp.arange(k * chunk) < n).reshape(k, chunk)
        emb = params["embed"]

        # checkpoint is what makes the chunking real: without it the
        # forward scan stacks each chunk's softmax residual and the
        # backward still carries the full [B, S-1, V] tensor (verified
        # by jaxpr inspection); with it the backward recomputes each
        # chunk's logits from [B, chunk, d]
        @jax.checkpoint
        def chunk_nll_sum(hc, tc, mask):
            # bf16 readout dot, f32 softmax math — the same deliberate
            # precision trade as apply_fn's logits (bf16-rounded
            # accumulation for a fast-bf16 backward); keeps the
            # recompute-and-backward matmuls off the all-f32 path
            logits = jnp.einsum("bcd,vd->bcv",
                                hc.astype(compute_dtype),
                                emb.astype(compute_dtype)
                                ).astype(jnp.float32)
            lse = jax.nn.logsumexp(logits, axis=-1)
            picked = jnp.take_along_axis(
                logits, tc[..., None], axis=-1)[..., 0]
            return ((lse - picked) * mask).sum()

        def chunk_nll(total, xs):
            hc, tc, mask = xs
            return total + chunk_nll_sum(hc, tc, mask), None

        total, _ = jax.lax.scan(chunk_nll, jnp.float32(0.0),
                                (hs, tg, valid))
        return total / (batch * n)

    def step(params, velocity, tokens):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens)
        new_v = jax.tree.map(
            lambda v, g: 0.9 * v - lr * g, velocity, grads)
        new_p = jax.tree.map(lambda p, v: p + v, params, new_v)
        return new_p, new_v, {"loss": loss}

    return step


def param_specs(params, seq_axis="seq"):
    """PartitionSpec pytree: Megatron TP rules for the block weights
    (qkv/up column-parallel on heads/hidden, out/down row-parallel),
    everything else replicated."""
    from veles_tpu.parallel import column_parallel, shard_dim
    rules = {
        "wqkv": shard_dim(5, 3),      # heads: column-parallel attention
        "wo": shard_dim(4, 1),        # heads in: row-parallel
        "w1": column_parallel(3),
        "b1": column_parallel(2),
        "w2": shard_dim(3, 1),        # hidden in: row-parallel
    }

    def walk(tree, out):
        for key, leaf in tree.items():
            if isinstance(leaf, dict):
                out[key] = {}
                walk(leaf, out[key])
            else:
                out[key] = rules.get(key, P())
        return out

    return walk(params, {})


def build_train(cfg=None, mesh=None, seq_axis="seq", lr=3e-4,
                compute_dtype=jnp.bfloat16, remat=True, seed=0,
                ce_chunk=128):
    """(params, velocity, jitted step).  With a mesh: DP×TP×SP shardings
    applied via in/out_shardings; without: plain single-device jit."""
    cfg = cfg or CONFIG
    params = init_params(cfg, seed=seed)
    velocity = jax.tree.map(numpy.zeros_like, params)
    step = make_train_step(cfg, mesh=mesh, seq_axis=seq_axis, lr=lr,
                           compute_dtype=compute_dtype, remat=remat,
                           ce_chunk=ce_chunk)
    if mesh is None:
        return params, velocity, jax.jit(step, donate_argnums=(0, 1))
    specs = param_specs(params, seq_axis)
    p_shard = jax.tree.map(
        lambda spec: NamedSharding(mesh, spec), specs,
        is_leaf=lambda x: isinstance(x, P))
    tok_shard = NamedSharding(mesh, P("data", seq_axis))
    jitted = jax.jit(
        step,
        in_shardings=(p_shard, p_shard, tok_shard),
        out_shardings=(p_shard, p_shard, replicated(mesh)),
        donate_argnums=(0, 1))
    return params, velocity, jitted


def train_step_flops(cfg, batch):
    """Analytic FLOPs of one LM train step (forward + backward + SGD
    update ≈ 3× the forward matmuls — the standard MFU convention;
    remat's forward recompute is deliberately NOT counted as useful
    work).

    Needed because :func:`apply_fn` scans the blocks: XLA's
    ``cost_analysis()`` counts the ``lax.scan`` body ONCE regardless of
    depth L, so compiled-cost FLOPs underreport by ~L (see the inner-
    scan caveat on ``veles_tpu.ops.timing.measure_fused_step``).
    Attention is counted causal-discounted (each token attends to ~S/2
    keys, matching what the flash kernel actually computes)."""
    d, L, S, V = cfg["dim"], cfg["layers"], cfg["seq_len"], cfg["vocab"]
    f = cfg["mlp_ratio"] * d
    per_token_layer = (
        2.0 * d * 3 * d          # qkv projection
        + 2.0 * S * d            # QK^T + AV, causal-averaged S/2 each
        + 2.0 * d * d            # output projection
        + 4.0 * d * f)           # mlp up + down
    per_token = L * per_token_layer + 2.0 * d * V   # tied readout
    return 3.0 * batch * S * per_token


def synthetic_tokens(cfg, batch, seed=0):
    rng = numpy.random.default_rng(seed)
    return rng.integers(0, cfg["vocab"],
                        (batch, cfg["seq_len"])).astype(numpy.int32)


def benchmark(cfg=None, batch=8, steps=5, mesh=None, **kwargs):
    """Tokens/sec of the fused LM train step."""
    import time
    cfg = cfg or CONFIG
    params, vel, step = build_train(cfg, mesh=mesh, **kwargs)
    tokens = synthetic_tokens(cfg, batch)
    params, vel, _m = step(params, vel, tokens)        # compile
    jax.block_until_ready(params)
    tic = time.perf_counter()
    for _ in range(steps):
        params, vel, metrics = step(params, vel, tokens)
    jax.block_until_ready(params)
    elapsed = time.perf_counter() - tic
    return steps * batch * cfg["seq_len"] / elapsed


if __name__ == "__main__":
    print("LM fused: %.0f tokens/sec" % benchmark())
