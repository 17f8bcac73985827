"""Mesh construction and sharding helpers.

The logical axes follow the scaling-book convention: ``data`` (DP),
``model`` (TP); pipeline/sequence axes are added by their consumers.
An axis size of -1 absorbs all remaining devices (mirrors
``TPUDevice.make_mesh``, :mod:`veles_tpu.backends`).

:func:`mesh_from_topology` is the knob-driven entry point
(``root.common.engine.pod.topology``) the pod runtime, the gen engine
and tests share, so none of them hand-rolls mesh construction — with
typed errors (:class:`MeshTopologyError`) for axis products the
attached devices cannot hold.
"""

import jax
import numpy
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


class MeshTopologyError(ValueError):
    """A requested topology cannot be laid out on the attached devices
    (axis product does not divide the device count, unknown axis spec,
    zero/negative size) — raised instead of silently training on fewer
    chips than the operator asked for."""


#: long-form axis spellings accepted in topology strings/dicts — the
#: mesh axes themselves stay short (``pipe`` matches the planner's
#: ``("pipe",)`` specs; ``expert`` is already canonical)
_AXIS_ALIASES = {"pipeline": "pipe", "pp": "pipe", "ep": "expert",
                 "tp": "model"}


def _parse_topology(topology):
    """Topology knob → ``{axis: size}``.  Accepted spellings:

    * ``None`` / ``""`` / ``"auto"`` — all devices on the ``data`` axis;
    * an int (or digit string) — that many ``data`` shards;
    * ``"DxM"`` — ``{"data": D, "model": M}`` (either may be ``-1``);
    * ``"data=2,pipeline=4"`` — comma-separated ``axis=size`` pairs
      for any axes; ``pipeline``/``pp`` normalize to ``pipe``,
      ``ep`` to ``expert``, ``tp`` to ``model``;
    * a dict ``{axis: size}`` (a Config node's ``to_dict()`` included;
      the same axis aliases apply).
    """
    if topology is None:
        return {"data": -1}
    if hasattr(topology, "to_dict"):
        topology = topology.to_dict()
    if isinstance(topology, dict):
        if not topology:
            return {"data": -1}
        return {_AXIS_ALIASES.get(str(k), str(k)): int(v)
                for k, v in topology.items()}
    if isinstance(topology, int):
        return {"data": int(topology)}
    text = str(topology).strip().lower()
    if text in ("", "auto"):
        return {"data": -1}
    if "=" in text:
        axes = {}
        for pair in text.split(","):
            name, _, size = pair.partition("=")
            name = _AXIS_ALIASES.get(name.strip(), name.strip())
            try:
                axes[name] = int(size)
            except ValueError:
                raise MeshTopologyError(
                    "cannot parse pod topology %r — axis pair %r is "
                    "not name=int" % (topology, pair))
        return axes
    parts = text.split("x")
    try:
        sizes = [int(p) for p in parts]
    except ValueError:
        raise MeshTopologyError(
            "cannot parse pod topology %r — want an int, 'DxM', "
            "'axis=size,…', 'auto', or {axis: size}" % (topology,))
    if len(sizes) == 1:
        return {"data": sizes[0]}
    if len(sizes) == 2:
        return {"data": sizes[0], "model": sizes[1]}
    raise MeshTopologyError(
        "pod topology %r has %d axes — only data[xmodel] is "
        "spellable as an 'x' string; spell more axes as "
        "'data=D,pipeline=S,expert=E' or pass {axis: size}"
        % (topology, len(sizes)))


def mesh_from_topology(topology=None, devices=None, require=None):
    """Build the pod mesh from the ``root.common.engine.pod.topology``
    knob (read fresh when ``topology`` is None) — THE mesh constructor
    PodRuntime, the serving engines and the tests share.

    Guarantees the loose :func:`make_mesh` does not:

    * every axis size is validated (``0``/negative → typed error, at
      most one ``-1`` wildcard);
    * the axis product must DIVIDE the device count — ``{"data": 3}``
      on 8 chips raises :class:`MeshTopologyError` naming the
      remainder instead of silently mis-gridding; an explicit product
      smaller than the device count is a deliberate sub-mesh (the
      leading devices), a wildcard absorbs ``devices // fixed``;
    * on one attached device, ``None``/``"auto"``/``1`` (and any
      wildcard) give the transparent all-ones mesh — single-device
      development configs run unchanged (``require`` axes are still
      present) — but a topology that ASKS for more than one device
      raises :class:`MeshTopologyError`: four chips' worth of work is
      never quietly run on the first one.

    ``require``: axis names that must exist in the result (added with
    size 1 when the topology omits them).
    """
    if topology is None:
        from veles_tpu.config import root
        node = root.common.engine.get("pod")
        topology = node.get("topology") if node else None
    axes = _parse_topology(topology)
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    for name in require or ():
        axes.setdefault(name, 1)
    if n <= 1:
        asked = int(numpy.prod([size for size in axes.values()
                                if size > 0] or [1]))
        if asked > 1:
            raise MeshTopologyError(
                "pod topology %r asks for %d devices and %d is "
                "attached (%s) — attach the devices, or spell the "
                "topology as auto / 1 to run on one"
                % (axes, asked, n,
                   ", ".join(str(d) for d in devices) or "none"))
        # auto / 1 on one device: the caller's program compiles for a
        # 1-sized mesh, which GSPMD lowers to the plain single-device
        # executable
        axes = {name: 1 for name in axes} or {"data": 1}
        return Mesh(numpy.array(devices or jax.devices()[:1]).reshape(
            [1] * len(axes)), tuple(axes))
    wild = [name for name, size in axes.items() if size == -1]
    if len(wild) > 1:
        raise MeshTopologyError(
            "pod topology %r has %d wildcard (-1) axes — at most one "
            "can absorb the remainder" % (axes, len(wild)))
    fixed = 1
    for name, size in axes.items():
        if size == -1:
            continue
        if size < 1:
            raise MeshTopologyError(
                "pod topology axis %r has size %d — sizes must be "
                "positive (-1 = absorb remainder)" % (name, size))
        fixed *= size
    if wild:
        if n % fixed:
            raise MeshTopologyError(
                "pod topology %r: fixed axis product %d does not "
                "divide %d attached devices (remainder %d) — the "
                "wildcard axis cannot absorb a fraction of a chip"
                % (axes, fixed, n, n % fixed))
        axes[wild[0]] = n // fixed
    elif fixed > n or n % fixed:
        raise MeshTopologyError(
            "pod topology %r: axis product %d does not divide %d "
            "attached devices (remainder %d) — match the attached "
            "topology, pick a divisor sub-mesh, or spell an axis as "
            "-1 to absorb the remainder"
            % (axes, fixed, n, n % fixed if fixed <= n else fixed - n))
    names = tuple(axes)
    shape = tuple(axes[name] for name in names)
    grid = numpy.array(devices[:int(numpy.prod(shape))]).reshape(shape)
    return Mesh(grid, names)


def make_mesh(axes=None, devices=None):
    """axes: {name: size}; -1 absorbs the remainder."""
    devices = list(devices if devices is not None else jax.devices())
    axes = dict(axes or {"data": -1})
    fixed = 1
    wild = None
    for name, size in axes.items():
        if size == -1:
            wild = name
        else:
            fixed *= size
    if wild is not None:
        axes[wild] = max(1, len(devices) // fixed)
    names = tuple(axes)
    shape = tuple(axes[n] for n in names)
    count = int(numpy.prod(shape))
    if count > len(devices):
        raise ValueError(
            "mesh %r needs %d devices, have %d" % (axes, count,
                                                   len(devices)))
    grid = numpy.array(devices[:count]).reshape(shape)
    return Mesh(grid, names)


def replicated(mesh):
    return NamedSharding(mesh, P())


def shard_batch(mesh, axis="data", ndim=2):
    """Batch-dim sharding: first dim split over ``axis``."""
    return NamedSharding(mesh, P(axis, *([None] * (ndim - 1))))


def shard_model(mesh, dim, ndim=2, axis="model"):
    """Tensor-parallel sharding of parameter dim ``dim``."""
    spec = [None] * ndim
    spec[dim] = axis
    return NamedSharding(mesh, P(*spec))
