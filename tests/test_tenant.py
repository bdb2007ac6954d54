"""In-pod tenant contract tests (tpushare.utils.tenant)."""

import pytest

from tpushare.plugin import const
from tpushare.utils import tenant


def set_env(monkeypatch, **kv):
    for k, v in kv.items():
        monkeypatch.setenv(k, v)


def test_read_tenant_env(monkeypatch):
    set_env(monkeypatch, **{
        const.ENV_TPU_VISIBLE_CHIPS: "1,2",
        const.ENV_HBM_LIMIT_BYTES: str(8 << 30),
        const.ENV_RESOURCE_BY_POD: "8",
        const.ENV_RESOURCE_BY_CONTAINER: "8",
        const.ENV_RESOURCE_BY_DEV: "16",
    })
    spec = tenant.read_tenant_env()
    assert spec.chips == [1, 2]
    assert spec.hbm_limit_bytes == 8 << 30
    assert spec.hbm_fraction == 0.5


def test_poisoned_env_raises(monkeypatch):
    set_env(monkeypatch, **{const.ENV_TPU_VISIBLE_CHIPS: "no-tpu-has-8GiB-to-run"})
    with pytest.raises(tenant.AllocationError):
        tenant.read_tenant_env()


def test_legacy_poisoned_env_raises(monkeypatch):
    monkeypatch.delenv(const.ENV_TPU_VISIBLE_CHIPS, raising=False)
    set_env(monkeypatch, **{const.ENV_TPU_VISIBLE_DEVICES: "no-gpu-has-4GiB-to-run"})
    with pytest.raises(tenant.AllocationError):
        tenant.read_tenant_env()


def test_apply_limits_sets_fraction(monkeypatch):
    monkeypatch.delenv("XLA_PYTHON_CLIENT_MEM_FRACTION", raising=False)
    set_env(monkeypatch, **{
        const.ENV_TPU_VISIBLE_CHIPS: "0",
        const.ENV_RESOURCE_BY_CONTAINER: "4",
        const.ENV_RESOURCE_BY_DEV: "16",
    })
    spec = tenant.apply_tenant_limits()
    assert spec.hbm_fraction == 0.25
    import os
    assert os.environ["XLA_PYTHON_CLIENT_MEM_FRACTION"] == "0.250"


def test_apply_limits_isolation_disabled(monkeypatch):
    monkeypatch.delenv("XLA_PYTHON_CLIENT_MEM_FRACTION", raising=False)
    set_env(monkeypatch, **{
        const.ENV_TPU_VISIBLE_CHIPS: "0",
        const.ENV_RESOURCE_BY_CONTAINER: "4",
        const.ENV_RESOURCE_BY_DEV: "16",
        const.ENV_DISABLE_ISOLATION: "true",
    })
    spec = tenant.apply_tenant_limits()
    assert spec.isolation_disabled
    import os
    assert "XLA_PYTHON_CLIENT_MEM_FRACTION" not in os.environ


def test_hbm_guard_breach(monkeypatch):
    guard = tenant.HbmGuard(limit_bytes=100, interval=0.01)
    guard._used_bytes = lambda: 500
    hits = []
    guard.on_breach = lambda used, limit: hits.append((used, limit))
    with guard:
        import time
        time.sleep(0.1)
    assert guard.breaches >= 1
    assert hits[0] == (500, 100)


def test_hbm_guard_no_limit_never_starts():
    guard = tenant.HbmGuard(limit_bytes=None)
    guard.start()
    assert guard._thread is None
    guard.stop()


@pytest.fixture
def restore_enforce_signal():
    import signal
    old = signal.getsignal(tenant._ENFORCE_SIGNAL)
    yield
    if tenant._enforcing_guard is not None:
        tenant._enforcing_guard.stop()
        tenant._enforcing_guard = None
    signal.signal(tenant._ENFORCE_SIGNAL, old)


def test_hbm_guard_enforce_raises_in_main_thread(restore_enforce_signal):
    """An enforcing guard turns an over-budget process into SoftHbmOom
    delivered to the MAIN thread (the in-process OOM-killer contract
    the isolation bench measures on chip)."""
    import time
    assert tenant._install_soft_oom_handler()
    guard = tenant.HbmGuard(limit_bytes=100, interval=0.01, enforce=True,
                            used_bytes_fn=lambda: 500)
    tenant._enforcing_guard = guard
    with pytest.raises(tenant.SoftHbmOom, match="500 bytes of 100"):
        with guard:
            deadline = time.time() + 5.0
            while time.time() < deadline:
                time.sleep(0.01)        # signal lands here
        raise AssertionError("guard never enforced")
    assert guard.breaches >= 1


def test_hbm_guard_enforce_cooldown(restore_enforce_signal):
    """Back-to-back breaches signal at most once per cooldown, so the
    tenant's MemoryError cleanup isn't itself re-signaled."""
    import time
    hits = []
    assert tenant._install_soft_oom_handler()
    guard = tenant.HbmGuard(limit_bytes=100, interval=0.01, enforce=True,
                            used_bytes_fn=lambda: 500)
    guard.ENFORCE_COOLDOWN_S = 10.0
    tenant._enforcing_guard = guard
    end = time.time() + 0.3
    with guard:
        while time.time() < end:
            try:
                while time.time() < end:
                    time.sleep(0.01)
            except tenant.SoftHbmOom:
                hits.append(time.time())
    assert len(hits) == 1
    assert guard.breaches > 1           # watchdog kept counting


def test_apply_limits_starts_enforcing_guard(monkeypatch,
                                             restore_enforce_signal):
    set_env(monkeypatch, **{
        const.ENV_TPU_VISIBLE_CHIPS: "0",
        const.ENV_RESOURCE_BY_CONTAINER: "8",
        const.ENV_RESOURCE_BY_DEV: "16",
        const.ENV_HBM_LIMIT_BYTES: str(8 << 30),
    })
    spec = tenant.apply_tenant_limits()
    assert spec.hbm_limit_bytes == 8 << 30
    guard = tenant._enforcing_guard
    assert guard is not None and guard.enforce and guard._thread is not None
    assert guard.limit == 8 << 30


def test_apply_limits_enforce_off(monkeypatch, restore_enforce_signal):
    set_env(monkeypatch, **{
        const.ENV_TPU_VISIBLE_CHIPS: "0",
        const.ENV_RESOURCE_BY_CONTAINER: "8",
        const.ENV_RESOURCE_BY_DEV: "16",
        const.ENV_HBM_LIMIT_BYTES: str(8 << 30),
        const.ENV_HBM_ENFORCE: "off",
    })
    tenant.apply_tenant_limits()
    assert tenant._enforcing_guard is None


def test_apply_limits_log_mode_no_signal(monkeypatch,
                                         restore_enforce_signal):
    set_env(monkeypatch, **{
        const.ENV_TPU_VISIBLE_CHIPS: "0",
        const.ENV_RESOURCE_BY_CONTAINER: "8",
        const.ENV_RESOURCE_BY_DEV: "16",
        const.ENV_HBM_LIMIT_BYTES: str(8 << 30),
        const.ENV_HBM_ENFORCE: "log",
    })
    tenant.apply_tenant_limits()
    guard = tenant._enforcing_guard
    assert guard is not None and not guard.enforce


def test_apply_limits_off_stops_previous_guard(monkeypatch,
                                               restore_enforce_signal):
    """Re-init with enforcement off must stop the earlier guard, not
    leave a 0.05s enforcer running against the operator's wishes."""
    base = {
        const.ENV_TPU_VISIBLE_CHIPS: "0",
        const.ENV_RESOURCE_BY_CONTAINER: "8",
        const.ENV_RESOURCE_BY_DEV: "16",
        const.ENV_HBM_LIMIT_BYTES: str(8 << 30),
    }
    set_env(monkeypatch, **base)
    tenant.apply_tenant_limits()
    first = tenant._enforcing_guard
    assert first is not None and first._thread is not None
    tenant.apply_tenant_limits(enforce="off")
    assert tenant._enforcing_guard is None
    assert first._stop.is_set()


def test_apply_limits_unknown_mode_fails_closed(monkeypatch,
                                                restore_enforce_signal):
    """A typo'd TPUSHARE_HBM_ENFORCE enforces rather than silently
    running the pod with zero isolation."""
    set_env(monkeypatch, **{
        const.ENV_TPU_VISIBLE_CHIPS: "0",
        const.ENV_RESOURCE_BY_CONTAINER: "8",
        const.ENV_RESOURCE_BY_DEV: "16",
        const.ENV_HBM_LIMIT_BYTES: str(8 << 30),
        const.ENV_HBM_ENFORCE: "enforced",   # not a valid mode
    })
    tenant.apply_tenant_limits()
    guard = tenant._enforcing_guard
    assert guard is not None and guard.enforce


def test_direct_enforce_guard_installs_handler(restore_enforce_signal):
    """HbmGuard(enforce=True).start() without apply_tenant_limits (the
    PARITY.md-advertised API) must install the SoftHbmOom handler
    itself — the signal's default disposition would kill the process."""
    import signal
    import time
    signal.signal(tenant._ENFORCE_SIGNAL, signal.SIG_DFL)
    guard = tenant.HbmGuard(limit_bytes=100, interval=0.01, enforce=True,
                            used_bytes_fn=lambda: 500)
    with pytest.raises(tenant.SoftHbmOom):
        with guard:
            deadline = time.time() + 5.0
            while time.time() < deadline:
                time.sleep(0.01)
        raise AssertionError("guard never enforced")


def test_hbm_guard_live_arrays_fallback():
    """Backends that report no allocator stats (the CPU) fall back to
    summing live on-device arrays."""
    import jax.numpy as jnp
    a = jnp.ones((1024,), jnp.float32)
    guard = tenant.HbmGuard(limit_bytes=1)
    used = guard._used_bytes()
    # Whichever source answered, a live 4 KiB array must be visible.
    assert used >= a.nbytes


# ---------------------------------------------------------------------------
# KV-block quota grant (ISSUE 9): the HBM-bytes contract extended to
# the unit the serving engine allocates
# ---------------------------------------------------------------------------

def test_kv_block_env_rides_tenant_spec(monkeypatch):
    set_env(monkeypatch, **{
        const.ENV_TPU_VISIBLE_CHIPS: "0",
        const.ENV_KV_BLOCK_RESERVE: "16",
        const.ENV_KV_BLOCK_LIMIT: "64",
    })
    spec = tenant.read_tenant_env()
    assert spec.kv_block_reserve == 16
    assert spec.kv_block_limit == 64


def test_kv_quota_env_builds_slo_spec(monkeypatch):
    from tpushare.slo.quota import TenantQuotaSpec
    set_env(monkeypatch, **{
        const.ENV_TPU_VISIBLE_CHIPS: "0",
        const.ENV_KV_BLOCK_RESERVE: "8",
        const.ENV_KV_BLOCK_LIMIT: "32",
    })
    assert tenant.kv_quota_env() == {
        "default": TenantQuotaSpec(reserve=8, ceiling=32)}
    # reserve-only: unlimited burst above the floor
    monkeypatch.delenv(const.ENV_KV_BLOCK_LIMIT)
    assert tenant.kv_quota_env() == {
        "default": TenantQuotaSpec(reserve=8, ceiling=None)}
    # no grant at all: None (zero-config = the unquota'd pool)
    monkeypatch.delenv(const.ENV_KV_BLOCK_RESERVE)
    assert tenant.kv_quota_env() is None


def test_resolve_tenant_quotas_merges_env_under_flag(monkeypatch):
    """The serving daemon merges the env grant UNDER --tenant-quota:
    per tenant the flag wins, but a flag naming only OTHER tenants
    must not silently discard the pod's own 'default' grant."""
    from tpushare.cli.serve import resolve_tenant_quotas
    from tpushare.slo.quota import TenantQuotaSpec
    set_env(monkeypatch, **{
        const.ENV_TPU_VISIBLE_CHIPS: "0",
        const.ENV_KV_BLOCK_RESERVE: "8",
        const.ENV_KV_BLOCK_LIMIT: "32",
    })
    # flag names another tenant: the env 'default' grant survives
    assert resolve_tenant_quotas("acme=16:64") == {
        "acme": TenantQuotaSpec(reserve=16, ceiling=64),
        "default": TenantQuotaSpec(reserve=8, ceiling=32)}
    # flag names 'default' itself: the flag wins
    assert resolve_tenant_quotas("default=0:4") == {
        "default": TenantQuotaSpec(reserve=0, ceiling=4)}
    # no flag: the env grant alone
    assert resolve_tenant_quotas("") == {
        "default": TenantQuotaSpec(reserve=8, ceiling=32)}
    # neither: None (the unquota'd pool)
    monkeypatch.delenv(const.ENV_KV_BLOCK_RESERVE)
    monkeypatch.delenv(const.ENV_KV_BLOCK_LIMIT)
    assert resolve_tenant_quotas("") is None


def test_kv_quota_env_poisoned_grant_raises(monkeypatch):
    set_env(monkeypatch, **{
        const.ENV_TPU_VISIBLE_CHIPS: "0",
        const.ENV_KV_BLOCK_RESERVE: "64",
        const.ENV_KV_BLOCK_LIMIT: "16",     # limit < reserve: poison
    })
    with pytest.raises(tenant.AllocationError):
        tenant.kv_quota_env()
