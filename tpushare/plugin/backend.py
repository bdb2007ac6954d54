"""TPU device discovery backends.

TPU-native replacement of the reference's L1/L2 NVML path
(/root/reference/pkg/gpu/nvidia/nvidia.go:44-86, which calls cgo/NVML
directly with no testing seam). Here discovery sits behind a ``Backend``
interface with four implementations:

- ``FakeBackend``     — env/arg-configured; drives every unit test and the
                        CPU dry-run config in BASELINE.md.
- ``SysfsBackend``    — reads ``/dev/accel*`` + ``/sys/class/accel`` (the
                        device nodes libtpu itself opens), optionally via
                        the native C++ helper (native/tpudisc.cpp).
- ``MetadataBackend`` — GCE metadata server ``accelerator-type`` lookup.
- ``JaxBackend``      — asks a live JAX runtime (grabs the chips; only for
                        benches/diagnostics, never the daemon hot path).

``auto_backend()`` chains them. Unlike the reference — which samples HBM
only from device 0 and assumes homogeneity (nvidia.go:67-69) — chips
carry per-chip HBM.
"""

from __future__ import annotations

import glob
import json
import logging
import os
import re
from dataclasses import dataclass, field
from typing import Optional, Sequence

log = logging.getLogger("tpushare.backend")

# Known single-host TPU topologies: accelerator-type -> (generation,
# chips per host, host ICI mesh (x, y, z), HBM bytes/chip, cores/chip).
# A v5e-4 host is a 2x2 ICI mesh (SURVEY.md §7 "hard parts").
_GIB = 1 << 30
KNOWN_TOPOLOGIES = {
    "v5litepod-1": ("v5e", 1, (1, 1, 1), 16 * _GIB, 1),
    "v5litepod-4": ("v5e", 4, (2, 2, 1), 16 * _GIB, 1),
    "v5litepod-8": ("v5e", 8, (2, 4, 1), 16 * _GIB, 1),
    "v5p-8": ("v5p", 4, (2, 2, 1), 95 * _GIB, 2),
    "v4-8": ("v4", 4, (2, 2, 1), 32 * _GIB, 2),
    "v6e-1": ("v6e", 1, (1, 1, 1), 32 * _GIB, 1),
    "v6e-4": ("v6e", 4, (2, 2, 1), 32 * _GIB, 1),
    "v6e-8": ("v6e", 8, (2, 4, 1), 32 * _GIB, 1),
}
_DEFAULT_HBM = {"v5e": 16 * _GIB, "v5p": 95 * _GIB, "v4": 32 * _GIB, "v6e": 32 * _GIB}
_DEFAULT_CORES = {"v5e": 1, "v5p": 2, "v4": 2, "v6e": 1}


def generation_from_kind(device_kind: str) -> str:
    """TPU generation ("v5e", ...) from a PJRT ``device_kind`` ("TPU v5
    lite"). An unknown kind is an error, never a default: every table
    keyed by generation (HBM, cores, peak FLOP/s, HBM bandwidth) would
    otherwise answer for a chip that is not the one in the machine."""
    kind = device_kind.lower().replace(" ", "")
    for gen in ("v6e", "v5p", "v5e", "v4"):
        if gen in kind:
            return gen
    for alias, gen in (("v5lite", "v5e"), ("v6lite", "v6e"), ("tpuv5", "v5p")):
        if alias in kind:
            return gen
    raise ValueError(f"unknown TPU device_kind {device_kind!r} "
                     f"(known generations: {sorted(_DEFAULT_HBM)})")


@dataclass(frozen=True)
class Chip:
    """One physical TPU chip on this host."""

    index: int                 # host-local chip index (what TPU_VISIBLE_CHIPS names)
    uuid: str                  # stable id used in fake-device IDs
    hbm_bytes: int
    cores: int
    coords: tuple              # (x, y, z) position in the host ICI mesh
    numa_node: int = 0
    healthy: bool = True
    # Host device node a tenant must open to reach this chip. The
    # reference never needs this — the NVIDIA container runtime mounts
    # devices from NVIDIA_VISIBLE_DEVICES on its own (allocate.go:114-128);
    # TPU has no such runtime hook, so Allocate must return DeviceSpec
    # entries built from these paths for non-privileged pods.
    device_path: str = ""


@dataclass(frozen=True)
class HostTopology:
    """Chip inventory + ICI mesh of one host (the 'device fabric'
    knowledge SURVEY.md §2 says replaces NVML's flat index list)."""

    generation: str            # "v5e", "v4", ...
    mesh: tuple                # host ICI mesh (x, y, z)
    chips: tuple = field(default_factory=tuple)
    # Device nodes every tenant on this host needs regardless of which
    # chip it got (the vfio layout's /dev/vfio/vfio control node).
    shared_device_paths: tuple = ()

    @property
    def chip_count(self) -> int:
        return len(self.chips)

    @property
    def total_hbm_bytes(self) -> int:
        return sum(c.hbm_bytes for c in self.chips)

    @property
    def total_cores(self) -> int:
        return sum(c.cores for c in self.chips)

    def chip_by_index(self, index: int) -> Chip:
        for c in self.chips:
            if c.index == index:
                return c
        raise KeyError(f"no chip with index {index}")

    def chip_by_uuid(self, uuid: str) -> Chip:
        for c in self.chips:
            if c.uuid == uuid:
                return c
        raise KeyError(f"no chip with uuid {uuid}")


def _mesh_coords(mesh: tuple) -> list:
    """Chip index -> ICI coordinate, row-major over (x, y, z)."""
    x, y, z = mesh
    return [(i % x, (i // x) % y, i // (x * y)) for i in range(x * y * z)]


def _build_topology(generation: str, count: int, mesh: tuple, hbm: int,
                    cores: int, uuid_prefix: str, numa_nodes: Optional[Sequence[int]] = None,
                    hbm_per_chip: Optional[Sequence[int]] = None,
                    indices: Optional[Sequence[int]] = None,
                    device_paths: Optional[Sequence[str]] = None,
                    shared_device_paths: Sequence[str] = ()) -> HostTopology:
    """``indices`` carries the real host device numbers when they are
    sparse (e.g. /dev/accel0 + /dev/accel2 with accel1 dead) — chip
    index is what TPU_VISIBLE_CHIPS addresses, so it must never be
    renumbered. numa/hbm/device-path lists are positional alongside it;
    when ``device_paths`` is absent the TPU-VM convention
    ``/dev/accel<index>`` is assumed."""
    coords = _mesh_coords(mesh)
    idxs = list(indices) if indices is not None else list(range(count))
    chips = tuple(
        Chip(
            index=idxs[i],
            uuid=f"{uuid_prefix}-{idxs[i]}",
            hbm_bytes=(hbm_per_chip[i] if hbm_per_chip else hbm),
            cores=cores,
            coords=coords[i] if i < len(coords) else (i, 0, 0),
            numa_node=(numa_nodes[i] if numa_nodes else 0),
            device_path=(device_paths[i] if device_paths
                         else f"/dev/accel{idxs[i]}"),
        )
        for i in range(count)
    )
    return HostTopology(generation=generation, mesh=mesh, chips=chips,
                        shared_device_paths=tuple(shared_device_paths))


class Backend:
    """Discovery seam. ``probe()`` returns the host topology or raises;
    ``available()`` is a cheap pre-check used by auto_backend()."""

    name = "abstract"

    def available(self) -> bool:
        raise NotImplementedError

    def probe(self) -> HostTopology:
        raise NotImplementedError

    def health_probe(self) -> HostTopology:
        """Periodic-poll variant of probe(). Default: a full re-probe.
        Backends whose probe is exclusive or expensive (libtpu takes
        the TPU runtime lock, so re-probing would race running
        tenants) override this with a side-band check."""
        return self.probe()


class FakeBackend(Backend):
    """Configurable fake (the seam the reference lacks — SURVEY.md §4).

    Env config: TPUSHARE_FAKE_CHIPS, TPUSHARE_FAKE_HBM_GIB,
    TPUSHARE_FAKE_MESH ("2x2"), TPUSHARE_FAKE_GENERATION,
    TPUSHARE_FAKE_UNHEALTHY (comma-separated chip indices).
    """

    name = "fake"

    def __init__(self, chips: Optional[int] = None, hbm_gib: Optional[float] = None,
                 mesh: Optional[tuple] = None, generation: Optional[str] = None,
                 cores: Optional[int] = None,
                 unhealthy: Optional[Sequence[int]] = None):
        env = os.environ
        self._chips = chips if chips is not None else int(env.get("TPUSHARE_FAKE_CHIPS", "0") or 0)
        self._hbm = int(float(hbm_gib if hbm_gib is not None
                              else env.get("TPUSHARE_FAKE_HBM_GIB", "16")) * _GIB)
        self._generation = generation or env.get("TPUSHARE_FAKE_GENERATION", "v5e")
        self._cores = cores if cores is not None else int(
            env.get("TPUSHARE_FAKE_CORES", str(_DEFAULT_CORES.get(self._generation, 1))))
        mesh_s = env.get("TPUSHARE_FAKE_MESH", "")
        if mesh is None and mesh_s:
            parts = [int(p) for p in re.split("[x,]", mesh_s)]
            mesh = tuple(parts + [1] * (3 - len(parts)))
        self._mesh = mesh
        self._unhealthy = set(unhealthy) if unhealthy is not None else {
            int(i) for i in env.get("TPUSHARE_FAKE_UNHEALTHY", "").split(",") if i.strip()
        }

    def available(self) -> bool:
        return self._chips > 0

    def probe(self) -> HostTopology:
        if self._chips <= 0:
            raise RuntimeError("FakeBackend not configured (set TPUSHARE_FAKE_CHIPS)")
        mesh = self._mesh or _default_mesh(self._chips)
        topo = _build_topology(self._generation, self._chips, mesh, self._hbm,
                               self._cores, uuid_prefix=f"faketpu-{self._generation}")
        if self._unhealthy:
            chips = tuple(
                Chip(**{**c.__dict__, "healthy": c.index not in self._unhealthy})
                for c in topo.chips
            )
            topo = HostTopology(topo.generation, topo.mesh, chips,
                                topo.shared_device_paths)
        return topo


def _default_mesh(count: int) -> tuple:
    return {1: (1, 1, 1), 2: (2, 1, 1), 4: (2, 2, 1), 8: (2, 4, 1), 16: (4, 4, 1)}.get(
        count, (count, 1, 1))


class SysfsBackend(Backend):
    """Discover chips from the accel device nodes libtpu opens.

    TPU VMs expose one ``/dev/accel<N>`` (older: ``/dev/vfio/<N>``) per
    chip with sysfs metadata under ``/sys/class/accel/accel<N>/device``.
    Prefers the native C++ helper (native/tpudisc.cpp via ctypes) and
    falls back to pure-Python scanning. Chip generation/HBM comes from
    the PCI device id table in the native lib or the metadata backend.
    """

    name = "sysfs"

    def __init__(self, dev_glob: str = "/dev/accel*", sysfs_root: str = "/sys/class/accel",
                 generation_hint: Optional[str] = None):
        self._dev_glob = dev_glob
        self._sysfs_root = sysfs_root
        self._generation_hint = generation_hint

    def _device_paths(self) -> list:
        # accel<N> (and bare <N> for the older /dev/vfio layout) — the
        # glob alone also matches noise like accel_ctl
        paths = [p for p in glob.glob(self._dev_glob)
                 if re.fullmatch(r"(accel)?\d+", os.path.basename(p))]
        return sorted(paths, key=_dev_index)

    def available(self) -> bool:
        return bool(self._device_paths())

    def probe(self) -> HostTopology:
        try:
            from tpushare.plugin import nativedisc
            topo = nativedisc.probe(self._dev_glob, self._sysfs_root,
                                    generation_hint=self._generation_hint)
            if topo is not None:
                return topo
        except Exception as e:  # native lib missing/unbuilt -> pure python
            log.debug("native discovery unavailable: %s", e)
        devs = self._device_paths()
        if not devs:
            raise RuntimeError("no /dev/accel* device nodes found")
        indices = [_dev_index(p) for p in devs]
        numa = [
            _read_int(os.path.join(self._sysfs_root, f"accel{i}", "device",
                                   "numa_node"), default=0)
            for i in indices
        ]
        # Older vfio layout exposes bare-number nodes under /dev/vfio/<N>
        # plus a shared /dev/vfio/vfio control node every tenant needs.
        shared = []
        if any(os.path.basename(p).isdigit() for p in devs):
            ctl = os.path.join(os.path.dirname(devs[0]), "vfio")
            if os.path.exists(ctl):
                shared.append(ctl)
        return build_topology_from_facts(
            indices, numa,
            generation=_generation_from_sysfs(self._sysfs_root) or "",
            generation_hint=self._generation_hint,
            device_paths=devs, shared_device_paths=shared)


def build_topology_from_facts(indices: Sequence[int],
                              numa_nodes: Sequence[int],
                              generation: str = "",
                              generation_hint: Optional[str] = None,
                              device_paths: Optional[Sequence[str]] = None,
                              shared_device_paths: Sequence[str] = ()) -> HostTopology:
    """One assembly path for discovered chip facts, shared by the native
    (nativedisc) and pure-Python sysfs probes so both emit identical
    uuids/HBM/mesh for the same host. Priority: detected generation >
    caller hint; neither (or one the tables do not know) is an error —
    a guessed generation would advertise another chip's HBM."""
    gen = generation or generation_hint or ""
    if gen not in _DEFAULT_HBM:
        raise RuntimeError(
            f"cannot tell the TPU generation of {list(device_paths or indices)} "
            f"(detected {generation!r}, hint {generation_hint!r}; known: "
            f"{sorted(_DEFAULT_HBM)})")
    count = len(indices)
    return _build_topology(gen, count, _default_mesh(count),
                           _DEFAULT_HBM[gen], _DEFAULT_CORES[gen],
                           uuid_prefix=f"tpu-{gen}-{_host_id()}",
                           numa_nodes=list(numa_nodes), indices=list(indices),
                           device_paths=(list(device_paths) if device_paths
                                         else None),
                           shared_device_paths=shared_device_paths)


def _dev_index(path: str) -> int:
    """Host device number from a node path (accel<N> or vfio <N>)."""
    return int(re.sub(r"\D", "", os.path.basename(path)) or 0)


def _read_int(path: str, default: int = 0) -> int:
    try:
        with open(path) as f:
            v = int(f.read().strip())
            return max(v, 0)  # sysfs numa_node is -1 when unknown
    except (OSError, ValueError):
        return default


def _generation_from_sysfs(root: str) -> Optional[str]:
    # PCI device ids of Google TPU accelerators (vendor 0x1ae0).
    table = {"0x0056": "v4", "0x0062": "v5e", "0x0063": "v5p", "0x006f": "v6e"}
    for dev in sorted(glob.glob(os.path.join(root, "accel*", "device", "device"))):
        try:
            with open(dev) as f:
                gen = table.get(f.read().strip().lower())
        except OSError:
            continue
        if gen is not None:
            return gen
    return None


def _host_id() -> str:
    try:
        with open("/etc/hostname") as f:
            return f.read().strip() or "host"
    except OSError:
        return "host"


class MetadataBackend(Backend):
    """GCE metadata server lookup of ``accelerator-type`` (e.g.
    "v5litepod-4") mapped through KNOWN_TOPOLOGIES."""

    name = "metadata"
    URL = ("http://metadata.google.internal/computeMetadata/v1/instance/"
           "attributes/accelerator-type")

    def __init__(self, url: Optional[str] = None, timeout: float = 2.0):
        self._url = url or os.environ.get("TPUSHARE_METADATA_URL", self.URL)
        self._timeout = timeout

    def _fetch(self) -> Optional[str]:
        import urllib.request
        req = urllib.request.Request(self._url, headers={"Metadata-Flavor": "Google"})
        try:
            with urllib.request.urlopen(req, timeout=self._timeout) as r:
                return r.read().decode().strip()
        except Exception:
            return None

    def available(self) -> bool:
        return self._fetch() is not None

    def probe(self) -> HostTopology:
        acc = self._fetch()
        if not acc:
            raise RuntimeError("GCE metadata accelerator-type unavailable")
        if acc not in KNOWN_TOPOLOGIES:
            raise RuntimeError(f"unknown accelerator-type {acc!r}")
        gen, count, mesh, hbm, cores = KNOWN_TOPOLOGIES[acc]
        return _build_topology(gen, count, mesh, hbm, cores,
                               uuid_prefix=f"tpu-{gen}-{_host_id()}")


class JaxBackend(Backend):
    """Probe through a live JAX/libtpu runtime. Accurate (true per-chip
    HBM via memory_stats) but *claims the chips*, so it must never run
    inside the serving daemon — bench/diagnostic use only."""

    name = "jax"

    def available(self) -> bool:
        try:
            import jax  # noqa: F401
            return True
        except Exception:
            return False

    def probe(self) -> HostTopology:
        import jax
        devs = [d for d in jax.devices() if d.platform == "tpu"]
        if not devs:
            raise RuntimeError("no TPU devices visible to JAX")
        gen = generation_from_kind(devs[0].device_kind)
        hbm_per_chip = [int(d.memory_stats()["bytes_limit"]) for d in devs]
        count = len(devs)
        return _build_topology(gen, count, _default_mesh(count), hbm_per_chip[0],
                               _DEFAULT_CORES[gen],
                               uuid_prefix=f"tpu-{gen}-{_host_id()}",
                               hbm_per_chip=hbm_per_chip)


class ChainBackend(Backend):
    """Probe backends in order, first success wins — so a wedged or
    held TPU runtime (libtpu probe) degrades to the sysfs/metadata
    static-table answer instead of blocking the daemon forever."""

    name = "chain"

    def __init__(self, backends: Sequence[Backend]):
        self.backends = list(backends)
        self._active: Optional[Backend] = None

    def available(self) -> bool:
        return any(b.available() for b in self.backends)

    def probe(self) -> HostTopology:
        errors = []
        for b in self.backends:
            if not b.available():
                continue
            try:
                topo = b.probe()
                self._active = b
                self._cross_check(topo)
                return topo
            except Exception as e:
                log.warning("backend %s probe failed: %s", b.name, e)
                errors.append(f"{b.name}: {e}")
        raise RuntimeError("all discovery backends failed: "
                           + "; ".join(errors or ["none available"]))

    # Static-table cross-validation (the PCI-id and KNOWN_TOPOLOGIES
    # tables decide advertised tpu-mem; a wrong entry would misreport
    # capacity on every node of that type, silently). When the sysfs
    # PCI-table answer won the chain and the GCE metadata server is
    # also reachable, compare them and shout on disagreement — the
    # metadata accelerator-type is authoritative on GCE. Disagreement
    # never blocks startup (air-gapped or non-GCE deployments have no
    # metadata), it makes the silent failure loud.
    disagreement: Optional[str] = None

    def _cross_check(self, topo: HostTopology) -> None:
        self.disagreement = None           # never report a stale mismatch
        try:
            self._cross_check_inner(topo)
        except Exception as e:             # a failed *check* must never
            log.debug("discovery cross-check skipped: %s", e)   # fail the probe

    def _cross_check_inner(self, topo: HostTopology) -> None:
        if self._active is None or self._active.name != "sysfs":
            return
        meta = next((b for b in self.backends if b.name == "metadata"), None)
        if meta is None:
            return
        try:
            # probe() directly (no available() pre-flight): each is a
            # bounded HTTP fetch, and one round-trip is enough to know.
            mt = meta.probe()
        except Exception:
            return                          # non-GCE / air-gapped: no check
        mismatches = []
        if mt.generation != topo.generation:
            mismatches.append(f"generation {topo.generation!r} (pci table) "
                              f"vs {mt.generation!r} (metadata)")
        if mt.chip_count != topo.chip_count:
            mismatches.append(f"chip_count {topo.chip_count} vs "
                              f"{mt.chip_count}")
        if (topo.chips and mt.chips
                and topo.chips[0].hbm_bytes != mt.chips[0].hbm_bytes):
            mismatches.append(f"hbm_bytes {topo.chips[0].hbm_bytes} vs "
                              f"{mt.chips[0].hbm_bytes}")
        if mismatches:
            self.disagreement = "; ".join(mismatches)
            log.error(
                "DISCOVERY TABLE MISMATCH (sysfs pci-id table vs GCE "
                "metadata): %s — advertised tpu-mem may be wrong for "
                "every node of this type; check KNOWN_TOPOLOGIES / the "
                "PCI id table in plugin/backend.py + native/tpudisc.cpp",
                self.disagreement)

    def health_probe(self) -> HostTopology:
        # Poll through whichever backend won the startup probe (its
        # health_probe knows how to re-check without re-acquiring the
        # runtime); fall back to a full chain probe before first use.
        if self._active is not None:
            return self._active.health_probe()
        return self.probe()


def auto_backend(prefer: Optional[str] = None) -> Backend:
    """Pick a backend: explicit name > fake-if-configured > measured
    (libtpu) with sysfs/metadata static-table fallback.

    The reference blocks forever when no GPU exists (gpumanager.go:39,46);
    callers get the same behavior by looping on this raising."""
    from tpushare.plugin.libtpudisc import LibtpuBackend
    by_name = {b.name: b for b in (
        FakeBackend(), LibtpuBackend(), SysfsBackend(), MetadataBackend(),
        JaxBackend())}
    prefer = prefer or os.environ.get("TPUSHARE_BACKEND", "")
    if prefer:
        if prefer not in by_name:
            raise ValueError(f"unknown backend {prefer!r}; one of {sorted(by_name)}")
        return by_name[prefer]
    if by_name["fake"].available():
        return by_name["fake"]
    chain = [by_name[n] for n in ("libtpu", "sysfs", "metadata")
             if by_name[n].available()]
    if len(chain) == 1:
        return chain[0]
    if chain:
        return ChainBackend(chain)
    raise RuntimeError("no TPU discovery backend available "
                       "(no TPUSHARE_FAKE_CHIPS, pjrtdisc helper, "
                       "/dev/accel*, or GCE metadata)")


def topology_to_json(topo: HostTopology) -> str:
    return json.dumps({
        "generation": topo.generation,
        "mesh": list(topo.mesh),
        "shared_device_paths": list(topo.shared_device_paths),
        "chips": [{"index": c.index, "uuid": c.uuid, "hbm_bytes": c.hbm_bytes,
                   "cores": c.cores, "coords": list(c.coords),
                   "numa_node": c.numa_node, "healthy": c.healthy,
                   "device_path": c.device_path}
                  for c in topo.chips],
    })
