"""Native discovery lib tests (native/tpudisc.cpp via ctypes) — the
TPU analog of the reference's go-nvml cgo seam."""

import os
import shutil
import subprocess

import pytest

from tpushare.plugin import nativedisc

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LIB = os.path.join(REPO, "native", "libtpudisc.so")


@pytest.fixture(scope="module", autouse=True)
def build_lib():
    if not os.path.exists(LIB):
        if shutil.which("g++") is None:
            pytest.skip("no g++ toolchain; native lib unbuilt")
        subprocess.run(["make", "-C", os.path.join(REPO, "native")], check=True)
    # reset module cache in case an earlier test marked load as failed
    nativedisc._LIB = None
    nativedisc._LOAD_FAILED = False


def fake_tree(tmp_path, n=4, pci="0x0062"):
    for i in range(n):
        (tmp_path / f"accel{i}").write_text("")
        dev = tmp_path / "sys" / f"accel{i}" / "device"
        dev.mkdir(parents=True)
        (dev / "numa_node").write_text(str(i % 2))
        (dev / "device").write_text(f"{pci}\n")
        (dev / "vendor").write_text("0x1ae0\n")
    return str(tmp_path), str(tmp_path / "sys")


def test_available():
    assert nativedisc.available()


def test_probe_raw(tmp_path):
    dev, sysr = fake_tree(tmp_path)
    raw = nativedisc.probe_raw(dev, sysr)
    assert len(raw["chips"]) == 4
    assert raw["chips"][1]["numa_node"] == 1
    assert raw["chips"][0]["generation"] == "v5e"


def test_probe_topology(tmp_path):
    dev, sysr = fake_tree(tmp_path, n=4)
    topo = nativedisc.probe(f"{dev}/accel*", sysr)
    assert topo.chip_count == 4
    assert topo.generation == "v5e"
    assert topo.mesh == (2, 2, 1)
    assert [c.numa_node for c in topo.chips] == [0, 1, 0, 1]


def test_probe_empty_dir_returns_none(tmp_path):
    assert nativedisc.probe(f"{tmp_path}/accel*", f"{tmp_path}/sys") is None


def test_probe_unknown_pci_is_an_error_not_v5e(tmp_path):
    """An unknown PCI id used to be called v5e and given 16 GiB; a
    guessed generation advertises another chip's HBM, so it raises —
    unless the caller supplies the generation as a hint."""
    dev, sysr = fake_tree(tmp_path, n=1, pci="0xdead")
    with pytest.raises(RuntimeError, match="generation"):
        nativedisc.probe(f"{dev}/accel*", sysr)
    topo = nativedisc.probe(f"{dev}/accel*", sysr, generation_hint="v6e")
    assert topo.generation == "v6e"


def test_sysfs_backend_uses_native(tmp_path):
    """SysfsBackend prefers the native path when the lib is loadable."""
    from tpushare.plugin.backend import SysfsBackend
    dev, sysr = fake_tree(tmp_path, n=2)
    be = SysfsBackend(dev_glob=f"{dev}/accel*", sysfs_root=sysr)
    topo = be.probe()
    assert topo.chip_count == 2
    assert topo.generation == "v5e"
