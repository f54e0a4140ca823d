"""The logical-axis sharding layer: one mesh factory + one rules table.

Before this module, every parallelism module hand-rolled its shardings:
``parallel/{spmd,tp,pipeline,ulysses,ring_attention,moe}.py`` each named
mesh axes by string convention, and hvdlint HVD008's suppression
inventory (18 findings across 8 files) was the coupling made visible.
This module is the T5X partitioning design (SNIPPETS.md [1][3]) applied
to that work list:

* **One vocabulary.** The physical axis names live HERE and only here —
  every other module imports them (``DATA_AXIS``/``ICI_AXIS``/
  ``DCN_AXIS`` and the per-role spellings below). HVD008 now hard-fails
  on any raw ``"hvd"``/``"ici"``/``"dcn"`` literal anywhere else.
* **One mesh factory.** :class:`LogicalMesh` builds the physical mesh
  from ``dp=8,tp=4,sp=2``-style axis stacks, layered on PR-10's
  :func:`~horovod_tpu.parallel.mesh.hybrid_mesh`/``slice_topology`` so
  DCN-aware placement falls out for free on multi-slice topologies, and
  falling back to a plain :func:`~horovod_tpu.parallel.mesh.make_mesh`
  on single-domain device sets (the CPU virtual-device testing path —
  the T5X ``cpu_fallback`` move, SNIPPETS.md [1]).
* **One rules table.** Logical tensor-dimension names (``batch``,
  ``heads``, ``embed``, ``mlp``, ``seq``, ``expert``, ``stage``, ...)
  map to physical mesh axes through an ordered rules registry; models
  annotate dimensions logically and :meth:`LogicalMesh.spec` resolves
  them against whatever stack is bound — a rule whose physical axis is
  absent from the mesh resolves to replicated, so any model composes
  with any parallelism stack.

The parallelism modules stay thin shims: their ``axis=`` parameters now
default to the bound mesh's role resolution (:func:`module_axis`), with
the historical per-module spellings (``"tp"``/``"pp"``/``"sp"``/
``"ep"``/``DATA_AXIS``) as the unbound fallback — bit-for-bit the
pre-registry behavior, equivalence-pinned in tests/test_logical.py.

Statically verified: hvdverify's HVV201 reconciles a program's declared
shardings against this rules table, HVV202 rejects collectives over
axes the bound LogicalMesh does not define, and HVV203 pins composed
stacks' collective schedules op-identical to the per-module reference
traces (docs/static_analysis.md).
"""

from __future__ import annotations

import contextlib
import math
from typing import Dict, List, Optional, Sequence, Tuple

from jax.sharding import Mesh, PartitionSpec as P

from horovod_tpu.common.exceptions import InvalidArgumentError

# --------------------------------------------------------------------------
# The axis vocabulary. The ONE definition site of the physical axis
# spellings — hvdlint HVD008 flags these literals everywhere else (the
# rule's own vocabulary set in tools/hvdlint/rules.py mirrors this), so
# the suppressions below are the only shipped ones outside that rule.

#: The flat data-parallel axis of the default 1-D mesh (every chip a rank).
DATA_AXIS = "hvd"  # hvdlint: disable=HVD008 (logical.py owns the axis vocabulary)
#: Fast-domain axis of the hybrid ICI x DCN mesh (within one slice).
ICI_AXIS = "ici"  # hvdlint: disable=HVD008 (logical.py owns the axis vocabulary)
#: Slow-domain axis of the hybrid mesh (across slices, over DCN).
DCN_AXIS = "dcn"  # hvdlint: disable=HVD008 (logical.py owns the axis vocabulary)

#: Physical axis spelling per parallelism role — the historical
#: per-module defaults, now named once. Roles are what the parallelism
#: modules ask for (:func:`module_axis`); logical axis NAMES (below) are
#: what model tensors are annotated with.
ROLE_AXES: Dict[str, str] = {
    "data": "dp",
    "tensor": "tp",
    "seq": "sp",
    "stage": "pp",
    "expert": "ep",
}

#: Unbound-fallback spelling per role: what each module's ``axis=``
#: parameter defaulted to before the registry existed. ``data`` falls
#: back to the flat 1-D mesh axis, not "dp" — the spmd harness predates
#: multi-axis stacks.
_LEGACY_ROLE_AXES: Dict[str, str] = dict(ROLE_AXES, data=DATA_AXIS)

#: The default logical-axis rules table (T5X-style; SNIPPETS.md [3] is
#: the GPT-J sibling). Ordered: the FIRST rule whose physical axis the
#: bound mesh defines wins; a ``None`` physical axis means replicated.
#: ``batch`` tries the composed-stack spelling first and falls back to
#: the flat 1-D harness axis so the same annotations resolve under both.
DEFAULT_RULES: Tuple[Tuple[str, Optional[str]], ...] = (
    ("batch", ROLE_AXES["data"]),
    ("batch", DATA_AXIS),
    ("heads", ROLE_AXES["tensor"]),
    ("kv", None),
    ("embed", None),
    ("mlp", ROLE_AXES["tensor"]),
    ("vocab", ROLE_AXES["tensor"]),
    ("seq", ROLE_AXES["seq"]),
    ("expert", ROLE_AXES["expert"]),
    ("stage", ROLE_AXES["stage"]),
)

#: Logical axis names each role may carry collectives for — how
#: :meth:`LogicalMesh.role_axis` resolves a role through a CUSTOM rules
#: table whose physical spellings differ from :data:`ROLE_AXES`.
_ROLE_LOGICAL: Dict[str, Tuple[str, ...]] = {
    "data": ("batch",),
    "tensor": ("heads", "mlp", "vocab"),
    "seq": ("seq",),
    "stage": ("stage",),
    "expert": ("expert",),
}

#: Canonical axis order of the config string (unknown axes sort after,
#: alphabetically) — `dp=8,tp=4,sp=2` is canonical, `tp=4,dp=8` is not.
_CANONICAL_ORDER: Tuple[str, ...] = (
    ROLE_AXES["data"], ROLE_AXES["tensor"], ROLE_AXES["seq"],
    ROLE_AXES["stage"], ROLE_AXES["expert"], DATA_AXIS, ICI_AXIS,
    DCN_AXIS)


# ------------------------------------------------------------ config string


def parse_mesh_config(config: str) -> Dict[str, int]:
    """Parse the canonical mesh config string (``"dp=8,tp=4,sp=2"``) into
    an ordered ``{axis: size}`` dict — the hvdplan input format (ROADMAP
    item 5a) and ``ServeConfig.mesh``'s spelling. ``-1`` is the
    :func:`~horovod_tpu.parallel.mesh.make_mesh` wildcard (at most one).
    """
    axes: Dict[str, int] = {}
    if not isinstance(config, str) or not config.strip():
        raise InvalidArgumentError(
            f"empty mesh config (expected e.g. 'dp=8,tp=4'): {config!r}")
    for part in config.split(","):
        part = part.strip()
        if "=" not in part:
            raise InvalidArgumentError(
                f"mesh config entry {part!r} is not name=size "
                f"(in {config!r})")
        name, _, size_s = part.partition("=")
        name = name.strip()
        if not name.isidentifier():
            raise InvalidArgumentError(
                f"mesh axis name {name!r} is not an identifier "
                f"(in {config!r})")
        if name in axes:
            raise InvalidArgumentError(
                f"duplicate mesh axis {name!r} in {config!r}")
        try:
            size = int(size_s.strip())
        except ValueError:
            raise InvalidArgumentError(
                f"mesh axis size {size_s!r} is not an integer "
                f"(in {config!r})") from None
        if size < 1 and size != -1:
            raise InvalidArgumentError(
                f"mesh axis {name}={size} must be >= 1 (or -1 wildcard)")
        axes[name] = size
    return axes


def format_mesh_config(axes: Dict[str, int]) -> str:
    """Render ``{axis: size}`` as the CANONICAL config string: known
    axes in dp/tp/sp/pp/ep order, unknown axes after them alphabetically
    — so two spellings of the same stack stamp identically into
    records."""
    def key(name: str):
        try:
            return (0, _CANONICAL_ORDER.index(name), name)
        except ValueError:
            return (1, 0, name)

    return ",".join(f"{n}={int(axes[n])}" for n in sorted(axes, key=key))


# --------------------------------------------------------------- the mesh


class LogicalMesh:
    """One physical mesh + one logical-axis rules table.

    ``axes`` maps physical axis name -> size in major-to-minor order
    (``-1`` wildcard as in :func:`~horovod_tpu.parallel.mesh.make_mesh`).
    On a multi-slice (DCN-present) device set the axes are split between
    the DCN and ICI levels of :func:`~horovod_tpu.parallel.mesh.
    hybrid_mesh` — leading axes go DCN-major until the slice count is
    consumed, the rest tile the slice — so ``dp=2,tp=4`` on a 2-slice
    topology puts dp across slices and tp on the ICI. Single-domain
    device sets (all CPU test meshes) build a plain ``make_mesh`` over
    the first ``prod(axes)`` devices: the virtual-device fallback.
    """

    def __init__(self, axes: Dict[str, int], *,
                 rules: Sequence[Tuple[str, Optional[str]]] = DEFAULT_RULES,
                 devices=None):
        from horovod_tpu.parallel import mesh as _mesh

        if not axes:
            raise InvalidArgumentError("LogicalMesh needs at least one axis")
        self.rules: Tuple[Tuple[str, Optional[str]], ...] = tuple(
            (str(l), p) for l, p in rules)
        import jax

        devices = (list(devices) if devices is not None
                   else list(jax.devices()))
        sizes = self._resolve_wildcard(dict(axes), len(devices))
        want = math.prod(sizes.values())
        if want > len(devices):
            # Fail-fast with the real arithmetic — without this the
            # overshoot surfaces as a cryptic make_mesh reshape error
            # (or worse, at first compile inside a consumer's jit).
            raise InvalidArgumentError(
                f"mesh axes {format_mesh_config(sizes)} need {want} "
                f"device(s) but only {len(devices)} are available")
        if want < len(devices):
            # Virtual sub-mesh (tests bind dp=2,tp=4 on however many
            # devices the host exposes): take a prefix, like the
            # hvdverify registry's _submesh.
            devices = devices[:want]
        if _mesh.dcn_present(devices):
            self.mesh = self._hybrid(sizes, devices, _mesh)
        else:
            self.mesh = _mesh.make_mesh(sizes, devices)
        self.axes: Dict[str, int] = {
            name: self.mesh.shape[name] for name in self.mesh.axis_names}

    @staticmethod
    def _resolve_wildcard(axes: Dict[str, int], n_devices: int
                          ) -> Dict[str, int]:
        wild = [name for name, s in axes.items() if s == -1]
        if len(wild) > 1:
            raise InvalidArgumentError("at most one axis may be -1")
        if wild:
            fixed = math.prod(s for s in axes.values() if s != -1)
            if fixed == 0 or n_devices % fixed != 0:
                raise InvalidArgumentError(
                    f"{n_devices} devices not divisible by {fixed}")
            axes[wild[0]] = n_devices // fixed
        return axes

    @staticmethod
    def _hybrid(sizes: Dict[str, int], devices, _mesh) -> Mesh:
        """Split the axis stack at the slice boundary: leading (major)
        axes multiply out to the slice count and go DCN; the rest tile
        one slice's chips and go ICI."""
        domains, per = _mesh.slice_topology(devices)
        dcn_axes: Dict[str, int] = {}
        acc = 1
        names = list(sizes)
        i = 0
        while i < len(names) and acc < domains:
            name = names[i]
            dcn_axes[name] = sizes[name]
            acc *= sizes[name]
            i += 1
        ici_axes = {name: sizes[name] for name in names[i:]}
        if acc != domains:
            raise InvalidArgumentError(
                f"mesh axes {sizes} do not factor at the slice boundary "
                f"of {domains} domain(s) x {per} chip(s): leading axes "
                f"multiply to {acc}, need {domains}")
        return _mesh.hybrid_mesh(ici_axes=ici_axes or None,
                                 dcn_axes=dcn_axes or None,
                                 devices=devices)

    @classmethod
    def from_config(cls, config: str, *,
                    rules: Sequence[Tuple[str, Optional[str]]]
                    = DEFAULT_RULES,
                    devices=None) -> "LogicalMesh":
        """Build from the canonical config string (``"dp=8,tp=4"``)."""
        return cls(parse_mesh_config(config), rules=rules, devices=devices)

    # ----------------------------------------------------------- resolution

    @property
    def axis_names(self) -> Tuple[str, ...]:
        return tuple(self.mesh.axis_names)

    @property
    def config(self) -> str:
        """The canonical config string of this mesh's axis stack."""
        return format_mesh_config(self.axes)

    def defines(self, axis: str) -> bool:
        """True when ``axis`` is a physical axis of this mesh — what
        hvdverify's HVV202 checks every traced collective against."""
        return axis in self.axes

    def axis(self, logical: str) -> Optional[str]:
        """Physical mesh axis for one logical axis name, via the first
        rule whose physical axis this mesh defines; ``None`` =
        replicated. Unknown logical names raise — a RAW physical axis
        here is exactly the coupling this layer removes (and the hvdlint
        HVD008 fixture shape)."""
        known = False
        for name, phys in self.rules:
            if name != logical:
                continue
            known = True
            if phys is None:
                return None
            if phys in self.axes:
                return phys
        if not known:
            raise InvalidArgumentError(
                f"unknown logical axis {logical!r}: not in the rules "
                f"table (known: {sorted({n for n, _ in self.rules})})")
        return None

    def spec(self, *logical_axes: Optional[str]) -> P:
        """Resolve logical tensor-dimension names to a PartitionSpec:
        ``spec("batch", None, "heads")`` -> e.g. ``P("dp", None, "tp")``
        on a dp x tp stack, ``P(None, None, None)``-free replication for
        dims whose rules map nowhere on this mesh."""
        resolved = [None if name is None else self.axis(name)
                    for name in logical_axes]
        # One physical axis may shard at most one dimension.
        used = [a for a in resolved if a is not None]
        dupes = {a for a in used if used.count(a) > 1}
        if dupes:
            raise InvalidArgumentError(
                f"logical axes {logical_axes} map {sorted(dupes)} onto "
                "more than one tensor dimension")
        return P(*resolved)

    def role_axis(self, role: str) -> Optional[str]:
        """Physical mesh axis for a parallelism ROLE ('data', 'tensor',
        'seq', 'stage', 'expert'): the conventional spelling when the
        mesh defines it, else the first rules-mapped logical axis of the
        role, else the flat 1-D axis for 'data', else ``None``."""
        if role not in _ROLE_LOGICAL:
            raise InvalidArgumentError(
                f"unknown parallelism role {role!r} "
                f"(known: {sorted(_ROLE_LOGICAL)})")
        conventional = ROLE_AXES[role]
        if conventional in self.axes:
            return conventional
        for logical in _ROLE_LOGICAL[role]:
            phys = self.axis(logical)
            if phys is not None:
                return phys
        if role == "data" and DATA_AXIS in self.axes:
            return DATA_AXIS
        return None


# ------------------------------------------------------------- bound mesh

_BOUND: List[LogicalMesh] = []


def bind(lm: LogicalMesh):
    """Context manager binding ``lm`` as the current logical mesh: the
    parallelism shims resolve their default axes against it
    (:func:`module_axis`), and hvdverify's HVV202 checks traced
    collectives against its axis set."""
    @contextlib.contextmanager
    def _ctx():
        _BOUND.append(lm)
        try:
            yield lm
        finally:
            _BOUND.pop()
    return _ctx()


def current_logical_mesh() -> Optional[LogicalMesh]:
    """The innermost :func:`bind`-ed mesh, or ``None``."""
    return _BOUND[-1] if _BOUND else None


def module_axis(role: str, override: Optional[str] = None) -> str:
    """Resolve a parallelism module's collective axis: an explicit
    ``axis=`` argument wins (the thin-shim contract — passing the
    historical literal is bit-for-bit the pre-registry path), else the
    bound LogicalMesh's role resolution, else the legacy per-module
    spelling. Raises when a bound mesh defines no axis for the role —
    composing a module onto a stack that cannot host it is a config
    error, not a silent fallback."""
    if override is not None:
        return override
    lm = current_logical_mesh()
    if lm is not None:
        axis = lm.role_axis(role)
        if axis is None:
            raise InvalidArgumentError(
                f"bound LogicalMesh {lm.config!r} defines no axis for "
                f"role {role!r}; add the axis to the mesh or pass axis= "
                "explicitly")
        return axis
    return _LEGACY_ROLE_AXES[role]


def logical_partition_specs(tree_logical_axes, lm: Optional[LogicalMesh]
                            = None):
    """Map a pytree of logical-axis tuples to PartitionSpecs via the
    (given or bound) mesh — the T5X ``logical_to_mesh_axes`` shape."""
    import jax

    lm = lm or current_logical_mesh()
    if lm is None:
        raise InvalidArgumentError(
            "logical_partition_specs needs a LogicalMesh (bind one or "
            "pass lm=)")
    return jax.tree_util.tree_map(
        lambda dims: lm.spec(*dims),
        tree_logical_axes,
        is_leaf=lambda x: isinstance(x, tuple))
