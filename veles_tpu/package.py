"""Model packaging: export a trained workflow for native inference.

Parity target: reference ``Workflow.package_export`` (``workflow.py:868-975``)
which writes ``contents.json`` + ``.npy`` weight files into a ``.zip`` or
``.tar.gz`` consumed by the C++ libVeles runtime
(``libVeles/src/workflow_loader.cc:41-49``, ``main_file_loader.h:100-136``).

TPU re-design (SURVEY §2.8 seam): the package carries BOTH
  * an interpretable unit list (``contents.json`` + ``.npy`` arrays) — the
    portable schema the native C++ runtime (``native/``) executes, and
  * optionally a serialized StableHLO module (``model.stablehlo``) produced
    by ``jax.export`` — the XLA-native artifact a PJRT consumer can run
    bit-identically to the trained graph.

Inference-time semantics (applied identically by :class:`PackagedRunner`
and the C++ runtime): dropout → identity (inverted dropout needs no test
scaling), stochastic pooling → probabilistic weighting (the Zeiler &
Fergus test-time procedure: Σ pᵢ·xᵢ over each window).
"""

import hashlib
import io
import json
import os
import tarfile
import zipfile

import numpy

FORMAT_VERSION = 1
#: int8 packages need a dequantizing reader — they declare version 2 so
#: pre-int8 readers fail closed instead of silently using raw codes
INT8_FORMAT_VERSION = 2
STABLEHLO_NAME = "model.stablehlo"
CONTENTS_NAME = "contents.json"


def _unit_export_entry(unit, array_refs):
    """Build the contents.json entry for one forward unit.

    ``array_refs``: dict array-name → file ref (filled by caller).
    """
    mapping = getattr(type(unit), "MAPPING", None)
    if mapping is None and type(unit).__name__ == "MeanDispNormalizer":
        mapping = "mean_disp"
    if not mapping:
        raise ValueError("unit %r (%s) is not packageable: no MAPPING"
                         % (unit, type(unit).__name__))
    entry = {"type": mapping, "name": unit.name or mapping,
             "config": {}, "arrays": array_refs}
    if mapping.startswith("all2all") or mapping == "softmax":
        entry["config"]["output_sample_shape"] = \
            list(unit.output_sample_shape)
        entry["config"]["activation"] = type(unit).ACTIVATION
        entry["config"]["is_softmax"] = mapping == "softmax"
        entry["config"]["include_bias"] = bool(unit.include_bias)
    elif mapping.startswith("conv"):
        entry["config"].update(
            n_kernels=unit.n_kernels, kx=unit.kx, ky=unit.ky,
            padding=list(unit.padding), sliding=list(unit.sliding),
            activation=type(unit).ACTIVATION,
            include_bias=bool(unit.include_bias))
        if getattr(unit, "grouping", 1) != 1:
            entry["config"]["grouping"] = int(unit.grouping)
    elif mapping.endswith("pooling"):
        entry["config"].update(kind=type(unit).KIND, kx=unit.kx,
                               ky=unit.ky, sliding=list(unit.sliding))
    elif mapping == "lrn":
        entry["config"].update(alpha=unit.alpha, beta=unit.beta,
                               k=unit.k, n=unit.n)
    elif mapping.startswith("activation_"):
        entry["config"].update(func=type(unit).FUNC, k=unit.k)
    elif mapping == "dropout":
        entry["config"].update(dropout_ratio=unit.dropout_ratio)
    elif mapping == "mean_disp":
        pass
    elif mapping in ("lstm", "rnn"):
        entry["config"].update(hidden_units=unit.hidden_units,
                               last_only=bool(unit.last_only),
                               include_bias=bool(unit.include_bias))
    elif mapping == "deconv":
        # transposed conv shares the paired Conv's weight layout
        # (ky, kx, C, K); its pure fn has no bias term
        left, right, top, bottom = unit.padding
        if not (0 <= left < unit.kx and 0 <= right < unit.kx
                and 0 <= top < unit.ky and 0 <= bottom < unit.ky):
            raise ValueError(
                "deconv with forward padding >= kernel size is not "
                "packageable (padding %r vs kernel (%d, %d))"
                % (unit.padding, unit.kx, unit.ky))
        entry["config"].update(
            n_kernels=unit.n_kernels, kx=unit.kx, ky=unit.ky,
            padding=list(unit.padding), sliding=list(unit.sliding),
            activation=type(unit).ACTIVATION, include_bias=False)
    elif mapping == "cutter":
        entry["config"].update(window=list(unit.window))
    elif mapping == "channel_splitter":
        entry["config"].update(start=int(unit.start),
                               count=unit.count)
    else:
        raise ValueError("unit type %r is not packageable" % mapping)
    return entry


def _quantize_int8(arr):
    """Per-output-channel symmetric int8: scale_j = max|w[..., j]|/127.
    Returns (int8 array, float32 scales over the last axis)."""
    flat = arr.reshape(-1, arr.shape[-1]) if arr.ndim > 1 \
        else arr.reshape(1, -1)
    scale = numpy.abs(flat).max(axis=0) / 127.0
    scale = numpy.where(scale == 0, 1.0, scale).astype(numpy.float32)
    q = numpy.clip(numpy.rint(arr / scale), -127, 127)
    return q.astype(numpy.int8), scale


def _collect_arrays(unit, precision):
    """name → numpy array (host-synced, precision-cast) for one unit.

    ``precision=8``: weights are per-output-channel symmetric int8
    (scales stored alongside as ``weights.scale``); bias/mean/disp stay
    float32 — the loaders (PackagedRunner and the native engine's
    workflow loader) dequantize at load, so compute stays float and
    the package is 4× smaller than fp32 (the same trade the fp16
    packages make at 2×)."""
    dtype = numpy.float16 if precision == 16 else numpy.float32
    out = {}
    # rdisp is MeanDispNormalizer's reciprocal dispersion; packaged as
    # "disp" (the runner multiplies, matching the unit's (x-mean)*rdisp)
    for attr, name in (("weights", "weights"), ("bias", "bias"),
                       ("mean", "mean"), ("rdisp", "disp")):
        vec = getattr(unit, attr, None)
        if vec is None or not vec:
            continue
        vec.map_read()
        out[name] = numpy.ascontiguousarray(vec.mem, dtype=dtype)
    if not getattr(unit, "include_bias", True):
        out.pop("bias", None)
    if out.get("weights") is not None and \
            getattr(unit, "weights_transposed", False) and \
            out["weights"].ndim == 2:
        # normalize to the package's canonical (fan-in, neurons)
        # layout so the golden model and native engine never need the
        # storage knob
        out["weights"] = numpy.ascontiguousarray(out["weights"].T)
    if precision == 8 and out.get("weights") is not None:
        q, scale = _quantize_int8(out["weights"])
        out["weights"] = q
        out["weights.scale"] = scale
    return out


def dequantize_arrays(arrays):
    """Resolve ``<name>.scale`` companions in-place: the int8 payload
    (already float-typed by the loader) is multiplied by its per-last-
    axis scales and the companion entry removed.  Shared by
    :class:`PackagedRunner`; the native engine applies the same rule in
    C++ (``native/src/workflow.cc``)."""
    for key in [k for k in arrays if k.endswith(".scale")]:
        base = key[:-len(".scale")]
        scale = arrays.pop(key)
        if base not in arrays:
            continue
        arr = arrays[base]
        if scale.size and arr.shape[-1] == scale.size:
            # the multiply's f32 output buffer is the only copy made
            arrays[base] = numpy.asarray(arr, numpy.float32) \
                * numpy.asarray(scale, numpy.float32)
        else:
            raise ValueError(
                "scale %r (%d entries) does not match %r last axis %r"
                % (key, scale.size, base, arr.shape))
    return arrays


def _npy_bytes(array):
    buf = io.BytesIO()
    numpy.save(buf, array, allow_pickle=False)
    return buf.getvalue()


def export_stablehlo(forwards, input_shape, dtype=numpy.float32):
    """Serialize the whole forward chain as StableHLO via ``jax.export``.

    Returns bytes, or None when jax.export is unavailable.
    """
    try:
        import jax
        from jax import export as jax_export
        fn = build_forward_fn(forwards)
        spec = jax.ShapeDtypeStruct(tuple(input_shape), dtype)
        exported = jax_export.export(jax.jit(fn))(spec)
        return exported.serialize()
    except Exception:
        # units without a jax pure form (e.g. MeanDispNormalizer) or an
        # unsupported chain: the interpretable package is still written
        return None


def build_forward_fn(forwards):
    """Compose the units' pure functions into one jittable forward fn
    (closure over host-synced params)."""
    import jax.numpy as jnp
    steps = []
    for unit in forwards:
        pure = type(unit).pure
        cfg = unit.pure_config()
        params = {}
        for attr, key in (("weights", "w"), ("bias", "b")):
            vec = getattr(unit, attr, None)
            if vec:
                vec.map_read()
                params[key] = jnp.array(vec.mem)   # a copy: memory.py's rule
        if not getattr(unit, "include_bias", True):
            params.pop("b", None)
        mapping = type(unit).MAPPING
        if mapping == "dropout":
            steps.append(lambda x: x)  # inference: identity
            continue
        if mapping.endswith("pooling") and "stochastic" in mapping:
            def step(x, p=params, c=cfg):
                raise NotImplementedError(
                    "stochastic pooling has no jax test-time export")
            steps.append(step)
            continue

        def step(x, pure=pure, p=params, c=cfg):
            return pure(p, x, **c)
        steps.append(step)

    def forward(x):
        for s in steps:
            x = s(x)
        return x
    return forward


def export_package(workflow_or_forwards, path, precision=32,
                   with_stablehlo=True, name=None):
    """Write a ``.zip`` or ``.tar.gz`` inference package.

    ``workflow_or_forwards``: a workflow exposing ``.forwards`` (e.g.
    :class:`veles_tpu.znicz.standard_workflow.StandardWorkflow`) or an
    explicit list of forward units in execution order.
    """
    if precision not in (8, 16, 32):
        raise ValueError("precision must be 8, 16 or 32")
    forwards = getattr(workflow_or_forwards, "forwards",
                       workflow_or_forwards)
    if not forwards:
        raise ValueError("nothing to export: no forward units")
    files = {}          # arcname → bytes
    units = []
    counter = 0
    for unit in forwards:
        arrays = _collect_arrays(unit, precision)
        refs = {}
        for aname, arr in sorted(arrays.items()):
            fname = "@%04d_%s.npy" % (
                counter, "x".join(str(d) for d in arr.shape) or "scalar")
            counter += 1
            files[fname] = _npy_bytes(arr)
            refs[aname] = fname
        units.append(_unit_export_entry(unit, refs))
    input_shape = list(forwards[0].input.shape) \
        if getattr(forwards[0], "input", None) is not None else None
    contents = {
        "format_version": INT8_FORMAT_VERSION
        if precision == 8 else FORMAT_VERSION,
        "framework": "veles_tpu",
        "name": name or getattr(workflow_or_forwards, "name", "model"),
        "precision": precision,
        "input_shape": input_shape,
        "units": units,
    }
    if precision == 8:
        # the StableHLO blob would embed the live fp32 weights — a
        # second, divergent weight set that also defeats the 4x size
        # reduction; int8 packages are interpretable-units only
        with_stablehlo = False
    if with_stablehlo and input_shape:
        blob = export_stablehlo(forwards, input_shape)
        if blob:
            files[STABLEHLO_NAME] = bytes(blob)
            contents["stablehlo"] = STABLEHLO_NAME
    # content checksum over every array/artifact file, stored INSIDE
    # contents.json so consumers can verify package integrity
    digest = hashlib.sha256()
    for arcname, data in sorted(files.items()):
        digest.update(arcname.encode())
        digest.update(data)
    contents["checksum"] = digest.hexdigest()
    files[CONTENTS_NAME] = json.dumps(
        contents, indent=1, sort_keys=True).encode()

    if path.endswith(".zip"):
        with zipfile.ZipFile(path, "w", zipfile.ZIP_DEFLATED) as z:
            for arcname, data in sorted(files.items()):
                z.writestr(arcname, data)
    elif path.endswith((".tar.gz", ".tgz")):
        with tarfile.open(path, "w:gz") as t:
            for arcname, data in sorted(files.items()):
                info = tarfile.TarInfo(arcname)
                info.size = len(data)
                t.addfile(info, io.BytesIO(data))
    else:
        raise ValueError("path must end with .zip, .tar.gz or .tgz")
    return contents


def _read_package(path):
    """arcname → bytes from a .zip/.tgz package or a directory."""
    files = {}
    if os.path.isdir(path):
        for fname in os.listdir(path):
            with open(os.path.join(path, fname), "rb") as f:
                files[fname] = f.read()
    elif path.endswith(".zip"):
        with zipfile.ZipFile(path) as z:
            for arcname in z.namelist():
                files[arcname] = z.read(arcname)
    else:
        with tarfile.open(path, "r:*") as t:
            for member in t.getmembers():
                if member.isfile():
                    files[member.name] = t.extractfile(member).read()
    return files


# ---------------------------------------------------------------------------
# Pure-numpy packaged inference — the golden model for the native runtime.

def _np_act(name, z):
    if name is None:
        return z
    if name == "tanh":
        return 1.7159 * numpy.tanh(0.6666 * z)
    if name == "sigmoid":
        return 1.0 / (1.0 + numpy.exp(-z))
    if name == "relu":  # znicz RELU = clipped softplus (fused.py _ACT)
        return numpy.log1p(numpy.exp(numpy.minimum(z, 30.0)))
    if name == "strict_relu":
        return numpy.maximum(z, 0.0)
    raise ValueError("unknown activation %r" % name)


def _np_act_unit(func, x, k):
    if func == "tanh":
        return 1.7159 * numpy.tanh(0.6666 * x)
    if func == "sigmoid":
        return 1.0 / (1.0 + numpy.exp(-x))
    if func == "relu":
        return numpy.log1p(numpy.exp(numpy.minimum(x, 30.0)))
    if func == "strict_relu":
        return numpy.maximum(x, 0.0)
    if func == "log":
        return numpy.log(x + numpy.sqrt(x * x + 1.0))
    if func == "tanhlog":
        t = 1.7159 * numpy.tanh(0.6666 * x)
        return numpy.where(
            numpy.abs(t) <= 1.7159 * 0.6666, t,
            numpy.sign(x) * numpy.log(
                numpy.abs(x * 0.6666 * 1.7159) + 1.0))
    if func == "sincos":
        odd = (numpy.arange(x.shape[-1]) % 2) == 1
        return numpy.where(odd, numpy.sin(x), numpy.cos(x))
    if func == "mul":
        return x * k
    raise ValueError("unknown func %r" % func)


def _np_softmax(z):
    z = z - z.max(axis=-1, keepdims=True)
    e = numpy.exp(z)
    return e / e.sum(axis=-1, keepdims=True)


def _np_deconv(x, w, padding, sliding):
    """Transposed conv matching ``znicz.misc_units.Deconv.pure``
    (``lax.conv_transpose``, HWOI, no kernel flip): dilate the input by
    the stride, pad with (k−1−p) per edge, then correlate."""
    left, right, top, bottom = padding
    sx, sy = sliding
    b_sz, h, wd, _k = x.shape
    ky, kx, c_out, _k2 = w.shape
    hd, wdd = (h - 1) * sy + 1, (wd - 1) * sx + 1
    pt, pb = ky - 1 - top, ky - 1 - bottom
    pl, pr = kx - 1 - left, kx - 1 - right
    if min(pt, pb, pl, pr) < 0:
        # the export gate rejects these; a hand-built package must not
        # silently flip numpy slices (eager conv_transpose would crop)
        raise ValueError(
            "deconv: forward padding %r >= kernel (%d, %d) is not "
            "supported by the packaged runner" % (padding, kx, ky))
    xp = numpy.zeros((b_sz, hd + pt + pb, wdd + pl + pr, x.shape[-1]),
                     numpy.float32)
    xp[:, pt:pt + hd:sy, pl:pl + wdd:sx, :] = x
    out_h = xp.shape[1] - ky + 1
    out_w = xp.shape[2] - kx + 1
    out = numpy.zeros((b_sz, out_h, out_w, c_out), numpy.float32)
    for dy in range(ky):
        for dx in range(kx):
            patch = xp[:, dy:dy + out_h, dx:dx + out_w, :]
            out += patch @ w[dy, dx].T          # (…, K) @ (K, C)
    return out


def _np_conv(x, w, b, padding, sliding, grouping=1):
    left, right, top, bottom = padding
    sx, sy = sliding
    ky, kx, cin, k = w.shape           # cin = per-group fan-in
    if grouping > 1:
        # output block i reads input channel group i (XLA's
        # feature_group_count semantics; native runtime matches)
        kpg = k // grouping
        outs = [
            _np_conv(x[..., gi * cin:(gi + 1) * cin],
                     w[..., gi * kpg:(gi + 1) * kpg], None,
                     padding, sliding)
            for gi in range(grouping)]
        out = numpy.concatenate(outs, axis=-1)
        return out if b is None else out + b
    x = numpy.pad(x, ((0, 0), (top, bottom), (left, right), (0, 0)))
    bsz, h, ww, _ = x.shape
    oh = (h - ky) // sy + 1
    ow = (ww - kx) // sx + 1
    # im2col → one big sgemm (mirrors the native runtime's strategy)
    cols = numpy.empty((bsz, oh, ow, ky * kx * cin), x.dtype)
    for iy in range(ky):
        for ix in range(kx):
            patch = x[:, iy:iy + oh * sy:sy, ix:ix + ow * sx:sx, :]
            cols[..., (iy * kx + ix) * cin:(iy * kx + ix + 1) * cin] = patch
    out = cols.reshape(-1, ky * kx * cin) @ w.reshape(ky * kx * cin, k)
    out = out.reshape(bsz, oh, ow, k)
    if b is not None:
        out = out + b
    return out


def _np_pool(x, kind, kx, ky, sliding):
    sx, sy = sliding
    b, h, w, c = x.shape
    oh = (h - ky) // sy + 1
    ow = (w - kx) // sx + 1
    patches = numpy.empty((b, oh, ow, ky * kx, c), x.dtype)
    for iy in range(ky):
        for ix in range(kx):
            patches[:, :, :, iy * kx + ix, :] = \
                x[:, iy:iy + oh * sy:sy, ix:ix + ow * sx:sx, :]
    if kind == "max":
        return patches.max(axis=3)
    if kind == "avg":
        return patches.mean(axis=3)
    mag = numpy.abs(patches)
    if kind == "maxabs":
        sel = numpy.argmax(mag, axis=3)
        return numpy.take_along_axis(
            patches, sel[:, :, :, None, :], axis=3)[:, :, :, 0, :]
    # stochastic{,abs}: test-time probabilistic weighting (Σ pᵢ·xᵢ)
    probs = mag / numpy.maximum(mag.sum(axis=3, keepdims=True), 1e-12)
    vals = mag if kind == "stochasticabs" else patches
    return (probs * vals).sum(axis=3)


def _np_lrn(x, alpha, beta, k, n):
    half = n // 2
    sq = x * x
    pads = [(0, 0)] * (x.ndim - 1) + [(half, n - 1 - half)]
    padded = numpy.pad(sq, pads)
    window = numpy.zeros_like(x)
    for i in range(n):
        sl = [slice(None)] * (x.ndim - 1) + \
            [slice(i, i + x.shape[-1])]
        window = window + padded[tuple(sl)]
    return x / (k + alpha * window) ** beta


class PackagedRunner(object):
    """Executes a package's unit list in pure numpy (fp32)."""

    def __init__(self, path_or_files):
        files = path_or_files if isinstance(path_or_files, dict) \
            else _read_package(path_or_files)
        self.contents = json.loads(files[CONTENTS_NAME].decode())
        if self.contents.get("format_version") not in (
                FORMAT_VERSION, INT8_FORMAT_VERSION):
            raise ValueError("unsupported package format %r"
                             % self.contents.get("format_version"))
        expected = self.contents.get("checksum")
        if expected:
            digest = hashlib.sha256()
            for arcname, data in sorted(files.items()):
                if arcname != CONTENTS_NAME:
                    digest.update(arcname.encode())
                    digest.update(data)
            if digest.hexdigest() != expected:
                raise ValueError("package checksum mismatch")
        self.units = []
        for entry in self.contents["units"]:
            arrays = {
                name: numpy.load(io.BytesIO(files[ref]),
                                 allow_pickle=False).astype(numpy.float32)
                for name, ref in entry["arrays"].items()}
            dequantize_arrays(arrays)
            self.units.append((entry["type"], entry["config"], arrays))

    @property
    def input_shape(self):
        return self.contents.get("input_shape")

    def run(self, x):
        x = numpy.asarray(x, numpy.float32)
        for utype, cfg, arrays in self.units:
            x = self._run_unit(utype, cfg, arrays, x)
        return x

    def _run_unit(self, utype, cfg, arrays, x):
        if utype.startswith("all2all") or utype == "softmax":
            h = x.reshape(len(x), -1)
            z = h @ arrays["weights"]
            if "bias" in arrays:
                z = z + arrays["bias"]
            if cfg.get("is_softmax"):
                z = _np_softmax(z)
            else:
                z = _np_act(cfg.get("activation"), z)
            return z.reshape([len(x)] + list(cfg["output_sample_shape"]))
        if utype.startswith("conv"):
            out = _np_conv(x, arrays["weights"], arrays.get("bias"),
                           cfg["padding"], cfg["sliding"],
                           cfg.get("grouping", 1))
            return _np_act(cfg.get("activation"), out)
        if utype.endswith("pooling"):
            return _np_pool(x, cfg["kind"], cfg["kx"], cfg["ky"],
                            cfg["sliding"])
        if utype == "lrn":
            return _np_lrn(x, cfg["alpha"], cfg["beta"], cfg["k"],
                           cfg["n"])
        if utype.startswith("activation_"):
            return _np_act_unit(cfg["func"], x, cfg.get("k", 1.0))
        if utype == "dropout":
            return x
        if utype == "mean_disp":
            return (x - arrays["mean"]) * arrays["disp"]
        if utype == "deconv":
            out = _np_deconv(x, arrays["weights"], cfg["padding"],
                             cfg["sliding"])
            return _np_act(cfg.get("activation"), out)
        if utype == "cutter":
            y, xo, h, w = cfg["window"]
            return numpy.ascontiguousarray(x[:, y:y + h, xo:xo + w, :])
        if utype == "channel_splitter":
            start = int(cfg["start"])
            count = cfg.get("count")
            stop = x.shape[-1] if count is None else start + int(count)
            return numpy.ascontiguousarray(x[..., start:stop])
        if utype in ("lstm", "rnn"):
            b, t, _d = x.shape
            h_units = int(cfg["hidden_units"])
            w = arrays["weights"]
            bias = arrays.get("bias")

            def sigmoid(z):
                return 1.0 / (1.0 + numpy.exp(-z))

            last_only = bool(cfg.get("last_only"))
            hh = numpy.zeros((b, h_units), numpy.float32)
            cc = numpy.zeros_like(hh) if utype == "lstm" else None
            ys = None if last_only else numpy.empty(
                (b, t, h_units), numpy.float32)
            for step in range(t):
                z = numpy.concatenate([x[:, step], hh], axis=1) @ w
                if bias is not None:
                    z = z + bias
                if utype == "lstm":
                    i, f, g, o = numpy.split(z, 4, axis=1)
                    cc = sigmoid(f) * cc + sigmoid(i) * numpy.tanh(g)
                    hh = sigmoid(o) * numpy.tanh(cc)
                else:
                    hh = numpy.tanh(z)
                if ys is not None:
                    ys[:, step] = hh
            return hh if last_only else ys
        raise ValueError("unknown packaged unit type %r" % utype)
