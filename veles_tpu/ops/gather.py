"""Minibatch gather: device-side sample selection by shuffled indices.

Parity target: ``ocl/fullbatch_loader.cl:5-30`` /
``cuda/fullbatch_loader.cu`` — gathers minibatch samples (and labels) from
the device-resident full dataset by an index vector, zero-padding the tail
of a short final batch.

TPU re-design.  A gather of rows is cheap only when a row is contiguous
on the device, and the chip does NOT hold an image-shaped set that way:
the compiler gives ``u8[25856, 227, 227, 3]`` the layout ``{0,2,3,1}``,
the SAMPLE dimension minor-most (it is the only one that fills the 128
lanes), so ``jnp.take`` along it first turns the whole 4 GB set around,
on every minibatch (19.9 ms of a 41 ms AlexNet step; ledger, PR 28).
Flattening the rows does not help (``u8[25856, 154587]`` gets ``{0,1}``),
and a Pallas kernel that begins with ``data.reshape(n, -1)`` pays the
same copy.  What helps is the FORM the set is held in
(:func:`resident_shape`): every row padded to whole ``(8, 128)`` tiles
and split into lanes, ``[n, tiles * 8, 128]``, which the chip lays out
rows-major, ``{2,1,0}``.  :func:`upload_rows` builds that form once, at
upload; :func:`take_rows` / :func:`take_rows_norm` index it and reshape
only the rows they took.  ``tests/test_chip_compile.py`` compiles the
gather for a described v5e both ways and holds the form to it.

On the form the gather is one XLA ``gather`` fusion over the rows taken.
It is the only path: a Pallas kernel that DMAs each row by a
scalar-prefetched index bought 0.08 ms a minibatch on the chip, 0.3% of
the AlexNet cell's step and under its spread (PERF.md, PR 30).
"""

import functools

import jax
import jax.numpy as jnp
import numpy

from veles_tpu import trace
from veles_tpu.backends import upload

LANES = 128
#: elements of the chip's (8 sublanes, 128 lanes) tile.  A ``[n, s, 128]``
#: array is laid out rows-major exactly when ``s`` is a multiple of 8,
#: for 8-, 16- and 32-bit elements alike; any other ``s`` puts the
#: samples into the sublanes
TILE = 8 * LANES
#: rows are placed into the form a chunk at a time, so that the set is
#: never on the device twice
CHUNK_BYTES = 1 << 28


def resident_shape(sample_shape):
    """The shape a row takes in the rows-major resident form, or None
    where the row keeps its own: THE rule, for upload and gather alike.

    A row is padded to whole tiles and split into lanes.  Rows that would
    grow by more than an eighth keep their shape (MNIST's 784 floats
    would become 1024; as ``[60000, 28, 28]`` the chip gathers them
    without a temporary already), and so do scalars (labels)."""
    elems = int(numpy.prod(sample_shape)) if sample_shape else 0
    padded = -(-elems // TILE) * TILE
    if elems == 0 or (padded - elems) * 8 > elems:
        return None
    return (padded // LANES, LANES)


@jax.tree_util.register_pytree_node_class
class ResidentRows(object):
    """A data set on the device in the rows-major form: ``form`` is
    ``[n, *resident_shape(sample_shape)]`` and stands for
    ``[n, *sample_shape]`` (what ``shape`` says); the padding is never
    visible.  A pytree, so it passes through ``jit``, ``device_put`` and
    a pod's shardings (the leading dimension is the samples') as the
    array it stands for would; :func:`take_rows` takes rows of it and a
    slice of it is a slice of its rows."""

    def __init__(self, form, sample_shape):
        self.form = form
        self.sample_shape = tuple(sample_shape)

    shape = property(lambda self: self.form.shape[:1] + self.sample_shape)
    dtype = property(lambda self: self.form.dtype)
    nbytes = property(lambda self: self.form.nbytes)

    def __getitem__(self, rows):
        if not isinstance(rows, slice):
            raise TypeError("a resident set is sliced by rows; "
                            "take_rows() gathers from it")
        return ResidentRows(self.form[rows], self.sample_shape)

    def __array__(self, dtype=None, copy=None):
        host = numpy.asarray(_samples(self.form, self.sample_shape))
        return host if dtype is None else host.astype(dtype)

    def tree_flatten(self):
        return (self.form,), self.sample_shape

    @classmethod
    def tree_unflatten(cls, sample_shape, children):
        return cls(children[0], sample_shape)


def _samples(rows, sample_shape):
    """Rows of the form (or flattened rows) back in their own shape."""
    if sample_shape is None or rows.shape[1:] == tuple(sample_shape):
        return rows
    elems = int(numpy.prod(sample_shape))
    return rows.reshape(rows.shape[0], -1)[:, :elems].reshape(
        rows.shape[:1] + tuple(sample_shape))


@functools.partial(jax.jit, donate_argnums=(0,))
def _place(form, chunk, start):
    pad = form.shape[1] * form.shape[2] - chunk.shape[1]
    rows = jnp.pad(chunk, ((0, 0), (0, pad))).reshape(
        chunk.shape[:1] + form.shape[1:])
    return jax.lax.dynamic_update_slice(form, rows, (start, 0, 0))


def _upload_form(flat, row_shape, put):
    """Rows ``flat`` ([n, elems] on the host) in the form on the ONE
    device ``put`` uploads to: chunks of the host's own rows (views, no
    second host copy) are uploaded and written in place into the form,
    the last chunk moved back over rows already written so that one
    program serves all."""
    n, elems = flat.shape
    per_chunk = CHUNK_BYTES // (elems * flat.dtype.itemsize)
    chunk = min(n, max(LANES, per_chunk // LANES * LANES))
    form = sent = None
    for start in range(0, n, chunk):
        start = min(start, n - chunk)
        piece = put(flat[start:start + chunk])
        if form is None:
            form = jnp.zeros((n,) + row_shape, flat.dtype,
                             device=piece.sharding)
        form = _place(form, piece, start)
        if sent is not None:
            # two chunks in flight at most
            sent.block_until_ready()
        sent = piece
    return form


def upload_rows(host, put, sharding=None):
    """The device copy of the data set ``host`` ([n, *sample_shape] on
    the host): a :class:`ResidentRows` where :func:`resident_shape`
    gives a form, the plain array where it does not.  ``put`` uploads
    to the one device; under a pod's ``sharding`` (by rows, or
    replicated) each device builds the form of its own rows."""
    from jax.sharding import NamedSharding, PartitionSpec
    n, sample_shape = host.shape[0], host.shape[1:]
    row_shape = resident_shape(sample_shape)
    elems = int(numpy.prod(sample_shape))
    padded = int(numpy.prod(row_shape)) if row_shape else elems
    stats = {"rows": n, "row_elems": elems, "padded_elems": padded,
             "bytes": n * padded * host.dtype.itemsize,
             "form": str((n,) + (row_shape or sample_shape))}
    with trace.span("loader", "upload", stats):
        if row_shape is None:
            return put(host) if sharding is None \
                else upload(host, sharding)
        flat = host.reshape(n, elems)
        if sharding is None:
            return ResidentRows(_upload_form(flat, row_shape, put),
                                sample_shape)
        if isinstance(sharding, NamedSharding):
            if any(sharding.spec[1:]):
                raise ValueError("a resident set is sharded by rows "
                                 "alone, not %r" % (sharding.spec,))
            sharding = NamedSharding(sharding.mesh,
                                     PartitionSpec(*sharding.spec[:1]))
        shape = (n,) + row_shape
        parts = []
        for device, index in \
                sharding.addressable_devices_indices_map(shape).items():
            lo, hi, _step = index[0].indices(n)
            parts.append(_upload_form(
                flat[lo:hi], row_shape,
                functools.partial(upload, placement=device)))
        return ResidentRows(jax.make_array_from_single_device_arrays(
            shape, sharding, parts), sample_shape)


def _rows_of(data):
    """``(array to gather from, the samples' shape)`` of a resident set
    in its form or of a plain array."""
    if isinstance(data, ResidentRows):
        return data.form, data.sample_shape
    return data, tuple(data.shape[1:])


def take_rows(data, indices):
    """``data[indices]`` along axis 0, ``[batch, *sample_shape]`` in the
    storage dtype, of a :class:`ResidentRows` or a plain array.
    Negative indices (the reference's "empty slot" marker for short
    batches) produce zero rows."""
    rows, shape = _rows_of(data)
    return _gather_jnp(rows, indices, sample_shape=shape)


def take_rows_norm(data, indices, norm):
    """Fused gather + affine normalize: float32
    ``data[indices]*scale + shift`` with negative indices producing
    ZERO rows (masking applies AFTER the normalize, so a short batch's
    padding stays 0 rather than ``shift``).

    This is the fullbatch loader's native-dtype head: the dataset stays
    resident in its storage dtype (e.g. uint8 pixels) and the first
    forward program receives normalized float32, the raw bytes read
    once.  ``norm`` is the loader's affine ``(scale, shift)`` pair
    (``NormalizerBase.as_affine``): scalars or flat per-feature
    arrays."""
    scale, shift = norm
    rows, shape = _rows_of(data)
    return _gather_norm_jnp(rows, indices,
                            jnp.asarray(scale, jnp.float32),
                            jnp.asarray(shift, jnp.float32),
                            sample_shape=shape)


@functools.partial(jax.jit, static_argnames=("sample_shape",))
@jax.named_scope("veles.loader.take_rows_norm")
def _gather_norm_jnp(data, indices, scale, shift, sample_shape=None):
    taken = _samples(jnp.take(data, jnp.maximum(indices, 0), axis=0),
                     sample_shape)
    flat = taken.reshape(taken.shape[0], -1).astype(jnp.float32)
    normed = (flat * scale.reshape(1, -1)
              + shift.reshape(1, -1)).reshape(taken.shape)
    mask = (indices >= 0).reshape((-1,) + (1,) * (taken.ndim - 1))
    return jnp.where(mask, normed, 0.0)


@functools.partial(jax.jit, static_argnames=("sample_shape",))
@jax.named_scope("veles.loader.take_rows")
def _gather_jnp(data, indices, sample_shape=None):
    # jitted: the eager form is 3 separate op dispatches per minibatch;
    # one compiled program per (shape, dtype) serves every batch.  The
    # scope names the gather's device operations wherever it is fused
    taken = _samples(jnp.take(data, jnp.maximum(indices, 0), axis=0),
                     sample_shape)
    mask = (indices >= 0).reshape((-1,) + (1,) * (taken.ndim - 1))
    return jnp.where(mask, taken, 0)
