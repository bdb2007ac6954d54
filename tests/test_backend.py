"""Discovery backend tests."""

import os

import pytest

from tpushare.plugin.backend import (
    KNOWN_TOPOLOGIES,
    FakeBackend,
    MetadataBackend,
    SysfsBackend,
    auto_backend,
    topology_to_json,
)


def test_fake_backend_defaults():
    topo = FakeBackend(chips=4).probe()
    assert topo.chip_count == 4
    assert topo.mesh == (2, 2, 1)
    assert topo.generation == "v5e"
    assert topo.total_hbm_bytes == 4 * 16 * (1 << 30)
    assert [c.coords for c in topo.chips] == [(0, 0, 0), (1, 0, 0), (0, 1, 0), (1, 1, 0)]


def test_fake_backend_env_config(monkeypatch):
    monkeypatch.setenv("TPUSHARE_FAKE_CHIPS", "8")
    monkeypatch.setenv("TPUSHARE_FAKE_HBM_GIB", "32")
    monkeypatch.setenv("TPUSHARE_FAKE_MESH", "2x4")
    monkeypatch.setenv("TPUSHARE_FAKE_GENERATION", "v6e")
    topo = FakeBackend().probe()
    assert topo.chip_count == 8
    assert topo.mesh == (2, 4, 1)
    assert topo.generation == "v6e"
    assert topo.chips[0].hbm_bytes == 32 * (1 << 30)


def test_fake_backend_unconfigured_raises():
    be = FakeBackend(chips=0)
    assert not be.available()
    with pytest.raises(RuntimeError):
        be.probe()


def test_sysfs_backend(tmp_path):
    for i in range(4):
        (tmp_path / f"accel{i}").write_text("")
        sys_dev = tmp_path / "sys" / f"accel{i}" / "device"
        sys_dev.mkdir(parents=True)
        (sys_dev / "numa_node").write_text(f"{i % 2}\n")
        (sys_dev / "device").write_text("0x0062\n")
    be = SysfsBackend(dev_glob=str(tmp_path / "accel*"),
                      sysfs_root=str(tmp_path / "sys"))
    assert be.available()
    topo = be.probe()
    assert topo.chip_count == 4
    assert topo.generation == "v5e"
    assert [c.numa_node for c in topo.chips] == [0, 1, 0, 1]
    assert topo.mesh == (2, 2, 1)
    # discovered node paths ride the chips (Allocate injects them as
    # DeviceSpec entries for non-privileged tenants)
    assert [c.device_path for c in topo.chips] == [
        str(tmp_path / f"accel{i}") for i in range(4)]
    assert topo.shared_device_paths == ()


def test_sysfs_backend_vfio_layout_shared_node(tmp_path):
    """Older vfio layout: bare-number per-chip nodes + the shared
    /dev/vfio/vfio control node every tenant needs."""
    vfio = tmp_path / "vfio"
    vfio.mkdir()
    for i in range(2):
        (vfio / str(i)).write_text("")
    (vfio / "vfio").write_text("")
    # No sysfs PCI id under this layout: without a hint the generation
    # is unknowable, and that is an error, not a v5e default.
    with pytest.raises(RuntimeError, match="generation"):
        SysfsBackend(dev_glob=str(vfio / "*"),
                     sysfs_root=str(tmp_path / "sys")).probe()
    be = SysfsBackend(dev_glob=str(vfio / "*"), sysfs_root=str(tmp_path / "sys"),
                      generation_hint="v5e")
    topo = be.probe()
    assert topo.chip_count == 2
    assert [c.device_path for c in topo.chips] == [str(vfio / "0"), str(vfio / "1")]
    assert topo.shared_device_paths == (str(vfio / "vfio"),)


def test_sysfs_backend_empty(tmp_path):
    be = SysfsBackend(dev_glob=str(tmp_path / "accel*"),
                      sysfs_root=str(tmp_path / "sys"))
    assert not be.available()
    with pytest.raises(RuntimeError):
        be.probe()


def test_metadata_backend_known_types():
    for acc, (gen, count, mesh, hbm, cores) in KNOWN_TOPOLOGIES.items():
        be = MetadataBackend()
        be._fetch = lambda a=acc: a  # stub network
        topo = be.probe()
        assert topo.chip_count == count
        assert topo.mesh == mesh
        assert topo.generation == gen
        assert topo.chips[0].hbm_bytes == hbm


def test_auto_backend_prefers_fake_when_configured(monkeypatch):
    monkeypatch.setenv("TPUSHARE_FAKE_CHIPS", "2")
    be = auto_backend()
    assert be.name == "fake"


def test_auto_backend_explicit(monkeypatch):
    monkeypatch.delenv("TPUSHARE_FAKE_CHIPS", raising=False)
    assert auto_backend(prefer="metadata").name == "metadata"
    with pytest.raises(ValueError):
        auto_backend(prefer="nvml")


def test_topology_json_roundtrip():
    import json
    topo = FakeBackend(chips=4).probe()
    data = json.loads(topology_to_json(topo))
    assert data["generation"] == "v5e"
    assert len(data["chips"]) == 4
    assert data["chips"][3]["coords"] == [1, 1, 0]


def test_sysfs_backend_ignores_non_chip_nodes(tmp_path, monkeypatch):
    """/dev noise like accel_ctl or accel9x must not count as chips
    (found by runtime probing; the glob alone over-matches)."""
    from tpushare.plugin import nativedisc
    for i in range(2):
        (tmp_path / f"accel{i}").write_text("")
        dev = tmp_path / "sys" / f"accel{i}" / "device"
        dev.mkdir(parents=True)
        (dev / "numa_node").write_text("0")
        (dev / "device").write_text("0x0062\n")
    (tmp_path / "accel9x").write_text("")
    (tmp_path / "accel_ctl").write_text("")
    monkeypatch.setattr(nativedisc, "_LIB", None)          # defeat load cache
    monkeypatch.setattr(nativedisc, "_LOAD_FAILED", True)  # pure-python path
    be = SysfsBackend(dev_glob=str(tmp_path / "accel*"),
                      sysfs_root=str(tmp_path / "sys"))
    assert be.probe().chip_count == 2


def test_sysfs_backend_sparse_indices_preserved(tmp_path, monkeypatch):
    """accel0 + accel2 (accel1 dead) must keep real host indices —
    TPU_VISIBLE_CHIPS addresses them, so renumbering misaddresses chips."""
    from tpushare.plugin import nativedisc
    for i in (0, 2):
        (tmp_path / f"accel{i}").write_text("")
        dev = tmp_path / "sys" / f"accel{i}" / "device"
        dev.mkdir(parents=True)
        (dev / "numa_node").write_text(str(i % 2))
        (dev / "device").write_text("0x0062\n")
    monkeypatch.setattr(nativedisc, "_LIB", None)
    monkeypatch.setattr(nativedisc, "_LOAD_FAILED", True)
    topo = SysfsBackend(dev_glob=str(tmp_path / "accel*"),
                        sysfs_root=str(tmp_path / "sys")).probe()
    assert [c.index for c in topo.chips] == [0, 2]
    assert [c.numa_node for c in topo.chips] == [0, 0]
    # native path preserves them too
    monkeypatch.setattr(nativedisc, "_LOAD_FAILED", False)
    if nativedisc.available():
        topo2 = SysfsBackend(dev_glob=str(tmp_path / "accel*"),
                             sysfs_root=str(tmp_path / "sys")).probe()
        assert [c.index for c in topo2.chips] == [0, 2]


def test_sysfs_backend_vfio_layout(tmp_path, monkeypatch):
    """Older /dev/vfio/<N> numbering also discovers chips."""
    from tpushare.plugin import nativedisc
    vfio = tmp_path / "vfio"
    vfio.mkdir()
    for i in range(2):
        (vfio / str(i)).write_text("")
    monkeypatch.setattr(nativedisc, "_LIB", None)
    monkeypatch.setattr(nativedisc, "_LOAD_FAILED", True)
    be = SysfsBackend(dev_glob=str(vfio / "*"), sysfs_root=str(tmp_path / "sys"),
                      generation_hint="v5e")
    assert be.available()
    assert be.probe().chip_count == 2
