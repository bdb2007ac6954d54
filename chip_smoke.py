#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that tpushare still starts on the chip.

Drives the system's main path once, through the entry points a user
calls, at the full width of Gemma-2B (18 layers, d=2048, 8 heads x 256,
MQA, vocab 256,128, bf16, seeded random weights — the one full-width
preset that fits a 16 GB chip):

  discovery          the plugin's real discovery chain (auto_backend(), no
                     fake env; native/ built here from the committed
                     sources) in a child that exits before any JAX child
                     starts — it takes the chip while it runs. Chip count
                     and per-chip HBM must agree with what JAX reports.
  child A (kernels)  every Pallas kernel the default dispatch reaches at
                     these shapes is compiled by Mosaic and compared with
                     its jnp reference; then one >=256-token prompt is
                     admitted through PagedSlotServer, one decode step is
                     taken, and the logits the server sampled from are
                     compared with transformer.forward(attn_impl=
                     "reference").
  child B (daemon)   the real ``python -m tpushare.cli.serve --preset
                     gemma_2b --platform tpu`` process, driven over HTTP
                     the way a client does, then SIGTERM and a drain.

The parent never imports JAX (a process that has touched JAX holds the
chip), gets the tenant env from the plugin's own Allocate fast path (a
whole-chip 16/16 grant) and starts its children one after another, never
two at once. Nothing on the path can hide the chip: both children force
``platform=tpu`` (which fails hard where no chip can be opened), every
recovery counter the engine keeps must read zero, and nothing here passes
``interpret=True``.

Last stdout line on success, and only then:
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Modes, asked for on the command line:
  --rehearse-cpu   the same script at --preset tiny on the CPU: checks the
                   script and the verdict logic here in the sandbox, says
                   ``platform: cpu``, and can never print the pass line
                   (exit code 3 when everything it can check held).
  --host4          builder-run, on a four-chip host: one engine over the
                   four chips (llama3_8b, --mesh tp=4), then four one-chip
                   Gemma-2B tenants side by side under the env the plugin
                   gives for each chip. Prints findings, not the pass line.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(REPO, "chiprun_out", "chip_smoke")
PASS_PLATFORM = "tpu"
RESULT_TAG = "KERNELS_RESULT "
DISCOVERY_TAG = "DISCOVERY "
VOCAB = {"tiny": 512, "gemma_2b": 256_128, "llama3_8b": 128_256}
#: daemon flags beyond --preset/--platform: existing knobs at their
#: documented values. --prefill-chunk 512 is the floor the daemon itself
#: recommends; it is what lets one >512-token prompt arrive as chunks that
#: fuse into a running decode batch (the fused admission tick).
DAEMON_FLAGS = ["--prefill-chunk", "512"]
BLOCK = 16                      # the daemon's default --block-size
LONG_PROMPT = 300               # >= 256: prefill pads to 512 -> flash_attention
CHUNKED_PROMPT = 700            # > --prefill-chunk: chunked + fused admission

#: /stats counters that must read zero after a clean run. Each one is a
#: recovery the engine performed: on a bring-up, a recovery is a failure
#: the engine hid (a Mosaic compile error, a VMEM RESOURCE_EXHAUSTED, a
#: deleted donated buffer all end in quarantine-and-replay).
ZERO_COUNTERS = ("engine_errors", "quarantines", "replays",
                 "engine_restarts", "deadline_breaches", "reshards",
                 "rejected", "preempted", "evict_errors")


def say(msg: str) -> None:
    print(msg, flush=True)


# -- verdict logic (pure; tests/test_chip_smoke.py) ------------------------

_BANNER_RE = re.compile(
    r"tpushare-serve on \S+:(?P<port>\d+) .*"
    r"platform=(?P<platform>\S+) device_kind='(?P<kind>[^']*)' "
    r"devices=(?P<count>\d+)")


def parse_banner(line: str):
    """The daemon's startup line -> {port, platform, kind, count}, or
    None when the line is not the banner."""
    m = _BANNER_RE.search(line)
    if not m:
        return None
    return {"port": int(m["port"]), "platform": m["platform"],
            "kind": m["kind"], "count": int(m["count"])}


def judge_banner(banner, want_platform: str, want_count: int = 1) -> list:
    if banner is None:
        return ["daemon printed no startup line naming its device"]
    fails = []
    if banner["platform"] != want_platform:
        fails.append(f"daemon runs on platform={banner['platform']}, "
                     f"not {want_platform}")
    if banner["count"] != want_count:
        fails.append(f"daemon sees {banner['count']} devices, "
                     f"want {want_count}")
    return fails


def judge_stats(stats: dict, *, mesh=None) -> list:
    """Failures a /stats body shows. ``mesh``: the configured mesh shape
    ({"tp": 4}) for a sharded daemon — it must still be the current one."""
    fails = [f"/stats {k}={stats[k]}" for k in ZERO_COUNTERS
             if stats.get(k)]
    if stats.get("degraded") is True:
        fails.append("/stats degraded=true")
    if stats.get("last_error"):
        fails.append(f"/stats last_error={stats['last_error']!r}")
    fpt = stats.get("fetches_per_tick")
    if fpt is None or fpt > 1.0:
        fails.append(f"/stats fetches_per_tick={fpt} (want <= 1.0)")
    if mesh is not None and stats.get("mesh_shape_current") != mesh:
        fails.append(f"/stats mesh_shape_current="
                     f"{stats.get('mesh_shape_current')}, want {mesh}")
    return fails


def judge_completion(name: str, status: int, body, vocab: int,
                     want_tokens: int) -> list:
    if status != 200:
        return [f"{name}: HTTP {status}: {body}"]
    toks = body.get("tokens") if isinstance(body, dict) else None
    if not toks:
        return [f"{name}: empty output: {body}"]
    bad = [t for t in toks
           if not isinstance(t, int) or isinstance(t, bool)
           or not 0 <= t < vocab]
    fails = []
    if bad:
        # -1 is the sampler's marker for a non-finite logits row.
        fails.append(f"{name}: tokens outside [0, {vocab}): {bad[:4]}")
    if len(toks) != want_tokens:
        fails.append(f"{name}: {len(toks)} tokens, asked for "
                     f"{want_tokens}")
    return fails


def judge_traffic(stats: dict) -> list:
    """The paths the smoke's traffic was built to reach, as /stats
    counts them."""
    fails = []
    if not stats.get("overlap_enabled"):
        fails.append("/stats overlap_enabled is not true")
    if not stats.get("chunked_admits"):
        fails.append("/stats chunked_admits=0: no chunked admission ran")
    if not stats.get("fused_ticks"):
        fails.append("/stats fused_ticks=0: no admission chunk rode a "
                     "decode batch")
    if not stats.get("prefix_hit_tokens"):
        fails.append("/stats prefix_hit_tokens=0: no prefix hit")
    return fails


def judge_discovery(topo: dict, device: dict, hbm_limit) -> list:
    """The plugin's view of the host against JAX's: the units the plugin
    would advertise come from this HBM figure."""
    fails = []
    if len(topo["chips"]) != device["count"]:
        fails.append(f"discovery saw {len(topo['chips'])} chips, JAX "
                     f"sees {device['count']}")
    hbm = sorted({c["hbm_bytes"] for c in topo["chips"]})
    if hbm != [hbm_limit]:
        fails.append(f"discovery's per-chip HBM {hbm} is not JAX's "
                     f"bytes_limit {hbm_limit}")
    return fails


# -- processes -------------------------------------------------------------

def child_env(platform: str, grant: dict) -> dict:
    """This process's env plus what the plugin injects for ``grant``."""
    env = dict(os.environ, **grant)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    if platform != "cpu":
        # Children must not inherit a JAX_PLATFORMS that would let them
        # settle on another backend; --platform/config.update forces it.
        env.pop("JAX_PLATFORMS", None)
    return env


def tenant_env(platform: str) -> dict:
    """The env a pod granted the whole chip would see (the plugin's own
    single-chip Allocate fast path, 16 of 16 units)."""
    from bench import plugin_env
    return child_env(platform, plugin_env(units_req=16))


def cache_entries() -> int:
    from tpushare.utils.compile_cache import compile_cache_dir
    try:
        return len(os.listdir(compile_cache_dir()))
    except OSError:
        return 0


def run_child(name: str, tag: str, commands: list, env: dict,
              timeout_s: float):
    """Run ``commands`` one after another (each has exited before the
    next starts) into chiprun_out/chip_smoke/<name>.log. Returns the JSON
    after ``tag`` on the last tagged line, or None — with the log's tail
    shown — when a command failed, timed out, or printed no such line."""
    log = os.path.join(OUT_DIR, f"{name}.log")
    t0, rc = time.time(), 0
    with open(log, "w") as f:
        for cmd in commands:
            try:
                rc = subprocess.run(cmd, env=env, cwd=REPO, stdout=f,
                                    stderr=subprocess.STDOUT,
                                    timeout=timeout_s).returncode
            except subprocess.TimeoutExpired:
                rc = "timeout"
            if rc != 0:
                break
    with open(log) as f:
        out = f.read()
    say(f"{name} child: rc={rc} in {time.time() - t0:.0f}s, "
        f"log {os.path.relpath(log, REPO)}")
    rows = [l for l in out.splitlines() if l.startswith(tag)]
    if rc != 0 or not rows:
        say(f"--- {name} child output (tail) ---")
        say(out[-6000:])
        return None
    return json.loads(rows[-1][len(tag):])


def run_discovery(env: dict):
    """Build native/ from the committed sources (never a stale binary:
    the .so and pjrtdisc are git-ignored) and run the plugin's discovery
    chain in a child. Returns (topology dict or None, failures); nothing
    here catches the chain's own failure."""
    topo = run_child("discovery", DISCOVERY_TAG, [
        ["make", "-B", "-C", os.path.join(REPO, "native")],
        [sys.executable, os.path.abspath(__file__), "--child", "discovery"],
    ], env, timeout_s=300)
    if topo is None:
        return None, ["discovery child failed"]
    say(f"discovery: backend {topo['backend']} saw generation "
        f"{topo['generation']}, {len(topo['chips'])} chip(s), HBM "
        f"{[c['hbm_bytes'] for c in topo['chips']]} bytes")
    return topo, []


def discovery_child() -> int:
    """What the plugin daemon does at startup, against the real host."""
    for k in [k for k in os.environ if k.startswith("TPUSHARE_FAKE")]:
        del os.environ[k]
    from tpushare.plugin import backend as B
    be = B.auto_backend()
    for b in getattr(be, "backends", [be]):
        say(f"backend {b.name}: available={b.available()}")
    topo = json.loads(B.topology_to_json(be.probe()))
    topo["backend"] = getattr(getattr(be, "_active", None), "name", be.name)
    print(DISCOVERY_TAG + json.dumps(topo), flush=True)
    return 0


def run_kernels_child(preset: str, platform: str, env: dict,
                      timeout_s: float):
    """Child A. Returns (result dict or None, failures)."""
    res = run_child("kernels", RESULT_TAG, [
        [sys.executable, os.path.abspath(__file__), "--child", "kernels",
         "--preset", preset, "--platform", platform]], env, timeout_s)
    if res is None:
        return None, ["kernels child failed"]
    return res, list(res["failures"])


class Daemon:
    """One ``tpushare.cli.serve`` process and an HTTP client for it."""

    def __init__(self, name: str, argv: list, env: dict):
        self.log = os.path.join(OUT_DIR, f"{name}.log")
        self._f = open(self.log, "w")
        self.t0 = time.time()
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "tpushare.cli.serve", *argv],
            env=env, cwd=REPO, stdout=self._f, stderr=subprocess.STDOUT)
        self.banner = None
        self.port = None

    def output(self) -> str:
        with open(self.log) as f:
            return f.read()

    def wait_banner(self, timeout_s: float):
        """Poll the log for the startup line; None if the process dies
        or the deadline passes first."""
        deadline = time.time() + timeout_s
        while time.time() < deadline:
            for line in self.output().splitlines():
                b = parse_banner(line)
                if b is not None:
                    self.banner, self.port = b, b["port"]
                    return b
            if self.proc.poll() is not None:
                return None
            time.sleep(0.5)
        return None

    def request(self, method: str, path: str, body=None,
                timeout_s: float = 600.0):
        data = None if body is None else json.dumps(body).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{self.port}{path}", data=data,
            method=method)
        try:
            with urllib.request.urlopen(req, timeout=timeout_s) as r:
                return r.status, json.loads(r.read() or b"null")
        except urllib.error.HTTPError as e:
            return e.code, e.read().decode(errors="replace")
        except (OSError, ValueError) as e:
            return 0, f"{type(e).__name__}: {e}"

    def complete(self, prompt: list, max_tokens: int):
        return self.request("POST", "/v1/completions",
                            {"prompt": prompt, "max_tokens": max_tokens})

    def stream(self, prompt: list, max_tokens: int, first_token=None,
               timeout_s: float = 600.0):
        """POST with stream:true; returns (status, {"tokens",
        "cached_prefix"} or an error string). ``first_token`` (an Event)
        is set when the first token frame arrives."""
        req = urllib.request.Request(
            f"http://127.0.0.1:{self.port}/v1/completions",
            data=json.dumps({"prompt": prompt, "max_tokens": max_tokens,
                             "stream": True}).encode(), method="POST")
        toks, done = [], None
        try:
            with urllib.request.urlopen(req, timeout=timeout_s) as r:
                ctype = r.headers.get("Content-Type", "")
                for raw in r:
                    line = raw.decode().strip()
                    if not line.startswith("data:"):
                        continue
                    ev = json.loads(line[5:])
                    if "token" in ev:
                        toks.append(ev["token"])
                        if first_token is not None:
                            first_token.set()
                    elif "error" in ev:
                        return r.status, f"stream error: {ev}"
                    elif ev.get("done"):
                        done = ev
                        break
                if "text/event-stream" not in ctype:
                    return r.status, f"not an event stream: {ctype!r}"
        except urllib.error.HTTPError as e:
            return e.code, e.read().decode(errors="replace")
        except (OSError, ValueError) as e:
            return 0, f"{type(e).__name__}: {e}"
        finally:
            if first_token is not None:
                first_token.set()       # never leave a waiter parked
        if done is None:
            return 200, f"stream ended without a done event ({toks})"
        return 200, {"tokens": toks,
                     "cached_prefix": done.get("cached_prefix")}

    def terminate(self, timeout_s: float = 60.0):
        """SIGTERM and wait for the drain; returns the exit code (None
        if it had to be killed)."""
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=timeout_s)
            except subprocess.TimeoutExpired:
                self.kill()
                return None
        return self.proc.returncode

    def kill(self):
        """Always called (a finally): no process outlives the smoke."""
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()
        self._f.close()


def prompt_of(seed: int, n: int, vocab: int) -> list:
    """n token ids from a seed (a small LCG: the parent stays off numpy's
    and JAX's generators, and the ids are the same on every machine)."""
    out, x = [], seed * 2654435761 % 2 ** 32 or 1
    for _ in range(n):
        x = (1103515245 * x + 12345) % 2 ** 31
        out.append(x % vocab)
    return out


def drive_daemon(d: Daemon, vocab: int) -> tuple:
    """The traffic of the smoke. Returns (failures, report dict)."""
    fails, rep = [], {}
    st, body = d.request("GET", "/healthz", timeout_s=30)
    if st != 200:
        return [f"/healthz: HTTP {st}: {body}"], rep

    # 1. One >=256-token prompt, alone: whole-prompt prefill pads to 512
    #    (flash_attention, Sq % 128 == 0), then paged_flash_decode steps.
    long_p = prompt_of(1, LONG_PROMPT, vocab)
    t0 = time.time()
    st, cold = d.complete(long_p, 8)
    rep["long_first_s"] = round(time.time() - t0, 1)    # compiles inside
    fails += judge_completion("long prompt (cold)", st, cold, vocab, 8)
    if fails:
        return fails, rep

    # 2. A streaming request that keeps decoding while four more arrive:
    #    five in flight together (continuous batching, overlapped tick),
    #    one of them longer than --prefill-chunk, so its chunks fuse into
    #    the running decode batch.
    started = threading.Event()
    results, want, threads = {}, {}, []

    def launch(name, max_tokens, fn, *args):
        want[name] = max_tokens
        t = threading.Thread(
            target=lambda: results.__setitem__(name, fn(*args)))
        threads.append(t)
        t.start()

    launch("stream", 96, d.stream, prompt_of(2, 24, vocab), 96, started)
    if not started.wait(timeout=600):
        fails.append("stream: no token within 600 s")
    for i, (n, mt) in enumerate([(CHUNKED_PROMPT, 12), (40, 16),
                                 (100, 16), (7, 16)]):
        launch(f"concurrent[{n}]", mt, d.complete,
               prompt_of(3 + i, n, vocab), mt)
    for t in threads:
        t.join(timeout=900)
    for name, mt in want.items():
        st, body = results.get(name, (0, "did not return within 900 s"))
        fails += judge_completion(name, st, body, vocab, mt)

    # 3. The long prompt again, twice: both take the prefix-hit path
    #    (cached_prefix > 0) and, being the same program over the same
    #    inputs, must give the same tokens. Cold vs hit is reported, not
    #    judged: the hit prefills only the 12-token suffix through the
    #    XLA reference attention while the cold run went through the
    #    flash kernel, and on random weights a bf16-level logit
    #    difference can flip a near-tied argmax.
    t0 = time.time()
    st1, hit1 = d.complete(long_p, 8)
    rep["long_hit_s"] = round(time.time() - t0, 1)
    t0 = time.time()
    st2, hit2 = d.complete(long_p, 8)
    rep["long_hit_repeat_s"] = round(time.time() - t0, 1)  # no compile
    fails += judge_completion("long prompt (hit)", st1, hit1, vocab, 8)
    fails += judge_completion("long prompt (hit, repeat)", st2, hit2,
                              vocab, 8)
    if not fails:
        if not hit1.get("cached_prefix"):
            fails.append(f"repeat of a {LONG_PROMPT}-token prompt had "
                         f"cached_prefix={hit1.get('cached_prefix')}")
        if hit1["tokens"] != hit2["tokens"]:
            fails.append(f"same greedy prompt, same path, different "
                         f"tokens: {hit1['tokens']} vs {hit2['tokens']}")
        rep["cached_prefix"] = hit1.get("cached_prefix")
        rep["cold_vs_hit_tokens_equal"] = sum(
            a == b for a, b in zip(cold["tokens"], hit1["tokens"]))
        rep["tokens_long"] = hit1["tokens"]

    st, stats = d.request("GET", "/stats", timeout_s=30)
    if st != 200:
        fails.append(f"/stats: HTTP {st}: {stats}")
        return fails, rep
    rep["stats"] = {k: stats.get(k) for k in (
        *ZERO_COUNTERS, "last_error", "degraded", "fetches_per_tick",
        "forwards_per_tick", "requests", "completed", "tokens_out",
        "steps", "work_ticks", "fused_ticks", "chunked_admits",
        "overlap_enabled", "pipeline_flushes", "prefix_hit_tokens",
        "prefix_prompt_tokens", "mesh_shape_current", "num_devices")}
    fails += judge_stats(stats) + judge_traffic(stats)
    return fails, rep


def run_daemon(preset: str, platform: str, env: dict,
               startup_s: float) -> tuple:
    """Child B. Returns (failures, report)."""
    vocab = VOCAB[preset]
    d = Daemon("daemon", ["--preset", preset, "--platform", platform,
                          "--port", "0", *DAEMON_FLAGS], env)
    rep = {}
    try:
        banner = d.wait_banner(startup_s)
        rep["startup_s"] = round(time.time() - d.t0, 1)
        fails = judge_banner(banner, platform)
        if banner is None:
            say("--- daemon output (tail) ---")
            say(d.output()[-6000:])
            return fails, rep
        rep["banner"] = banner
        if not fails:
            more, drep = drive_daemon(d, vocab)
            fails += more
            rep.update(drep)
        rc = d.terminate()
        rep["drain_rc"] = rc
        if rc != 0:
            fails.append(f"daemon exit code after SIGTERM: {rc}")
        tail = [l for l in d.output().splitlines()
                if l.startswith(("SIGTERM", "drained"))]
        rep["drain_lines"] = tail
        if fails:
            say("--- daemon output (tail) ---")
            say(d.output()[-4000:])
        return fails, rep
    finally:
        d.kill()


# -- child A: kernels and the slot server against their references --------

def kernels_child(preset: str, platform: str) -> int:
    """Runs in its own process (it owns the chip while it lives). Prints
    progress, then one RESULT_TAG line with everything it measured."""
    import jax
    jax.config.update("jax_platforms", platform)
    from tpushare.utils.compile_cache import enable_compile_cache
    on_chip = jax.default_backend() != "cpu"
    if on_chip:
        enable_compile_cache()
    import jax.numpy as jnp
    import numpy as np
    import jaxlib
    from tpushare.models import transformer as tf
    from tpushare.models.paged import PagedSlotServer
    from tpushare.ops.attention import mha_reference
    from tpushare.ops.flash_attention import (
        flash_attention, flash_eligible, paged_decode_eligible,
        paged_flash_decode)

    devs = jax.devices()
    try:
        import libtpu
        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = None
    res = {"device": {"platform": devs[0].platform,
                      "kind": devs[0].device_kind, "count": len(devs)},
           "jax": jax.__version__, "jaxlib": jaxlib.__version__,
           "libtpu": libtpu_version, "failures": [], "kernels": {}}
    fails = res["failures"]
    say(f"device: platform={devs[0].platform} "
        f"kind={devs[0].device_kind!r} count={len(devs)}; "
        f"jax {jax.__version__}, libtpu {libtpu_version}")

    cfg = {"tiny": tf.tiny, "gemma_2b": tf.gemma_2b}[preset]()
    assert cfg.vocab_size == VOCAB[preset]
    H, Hkv, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    n_slots, n_blocks = 8, 256              # the daemon's defaults
    pad = 512                               # LONG_PROMPT's prefill bucket

    def timed(fn, *args):
        """(value, compile seconds, run seconds) of a jitted fn."""
        t0 = time.perf_counter()
        exe = fn.lower(*args).compile()
        t1 = time.perf_counter()
        out = jax.block_until_ready(exe(*args))
        return out, t1 - t0, time.perf_counter() - t1

    def max_err(a, b):
        return float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                     - b.astype(jnp.float32))))

    # Kernel tolerance: kernel and reference both take bf16 inputs,
    # accumulate in f32 and round the output to bf16. The outputs are
    # convex combinations of N(0,1) values, |out| < 4, where one bf16
    # ulp is 2^-8 * 4 = 0.016; the kernel's f32 products may run as
    # bf16 MXU passes while the reference runs at "highest". 2e-2 is one
    # ulp at the top of the range (the v5e measured 0.0078, one ulp at
    # |out| ~ 2) and far below a masking or indexing mistake (O(1)).
    KERNEL_ATOL = 2e-2
    ks = jax.random.split(jax.random.PRNGKey(7), 6)
    shapes = {
        "flash": flash_eligible(
            jax.ShapeDtypeStruct((1, pad, H, D), cfg.dtype),
            jax.ShapeDtypeStruct((1, pad, Hkv, D), cfg.dtype),
            jax.ShapeDtypeStruct((1, pad, Hkv, D), cfg.dtype)),
        "paged_decode": paged_decode_eligible(
            jax.ShapeDtypeStruct((n_slots, 1, H, D), cfg.dtype),
            jax.ShapeDtypeStruct((n_blocks, BLOCK, Hkv, D), cfg.dtype),
            quantized=False, max_ctx=n_blocks * BLOCK)}
    res["dispatch_predicates"] = shapes
    say(f"dispatch predicates at serving shapes: {shapes}")
    if on_chip:
        for name, ok in shapes.items():
            if not ok:
                fails.append(f"{name}_eligible is False at the "
                             f"{preset} serving shapes")

        # flash_attention at the prefill shape, q_offset traced.
        q = jax.random.normal(ks[0], (1, pad, H, D), cfg.dtype)
        k = jax.random.normal(ks[1], (1, pad, Hkv, D), cfg.dtype)
        v = jax.random.normal(ks[2], (1, pad, Hkv, D), cfg.dtype)
        fl = jax.jit(lambda q, k, v, off: flash_attention(
            q, k, v, causal=True, q_offset=off, scale=cfg.attn_scale))
        out, c_s, r_s = timed(fl, q, k, v, jnp.int32(0))
        with jax.default_matmul_precision("highest"):
            ref = mha_reference(q, k, v, causal=True, q_offset=0,
                                scale=cfg.attn_scale)
        err = max_err(out, ref)
        res["kernels"]["flash_attention"] = {
            "max_err": err, "compile_s": round(c_s, 2),
            "run_s": round(r_s, 4), "shape": [1, pad, H, D]}
        say(f"flash_attention [1,{pad},{H},{D}] traced q_offset: "
            f"max_err {err:.4g} (atol {KERNEL_ATOL}), compile "
            f"{c_s:.1f}s, run {r_s * 1e3:.1f} ms")
        if not err <= KERNEL_ATOL:
            fails.append(f"flash_attention disagrees with mha_reference:"
                         f" max_err {err}")

        # paged_flash_decode on a bf16 pool at the daemon's block size,
        # against the gathered-view branch of transformer.forward.
        mb = n_blocks
        pk = jax.random.normal(ks[3], (n_blocks, BLOCK, Hkv, D), cfg.dtype)
        pv = jax.random.normal(ks[4], (n_blocks, BLOCK, Hkv, D), cfg.dtype)
        qd = jax.random.normal(ks[5], (n_slots, 1, H, D), cfg.dtype)
        pos = jnp.asarray([LONG_PROMPT, 5, 23, 16, 15, 100, 0, 477],
                          jnp.int32)
        table_np = np.full((n_slots, mb), -1, np.int32)
        nxt = 0
        for b in range(n_slots):            # slot b owns its own pages
            need = int(pos[b]) // BLOCK + 1
            table_np[b, :need] = np.arange(nxt, nxt + need)
            nxt += need
        table = jnp.asarray(table_np)
        pd = jax.jit(lambda q, pk, pv, t, p: paged_flash_decode(
            q, pk, pv, t, p, scale=cfg.attn_scale))
        out, c_s, r_s = timed(pd, qd, pk, pv, table, pos)
        trash = n_blocks - 1
        safe = jnp.where(table >= 0, table, trash)
        kd = pk[safe].reshape(n_slots, mb * BLOCK, Hkv, D)
        vd = pv[safe].reshape(n_slots, mb * BLOCK, Hkv, D)
        kv_mask = jnp.arange(mb * BLOCK)[None, :] <= pos[:, None]
        with jax.default_matmul_precision("highest"):
            ref = mha_reference(qd, kd, vd, causal=False, kv_mask=kv_mask,
                                scale=cfg.attn_scale)
        err = max_err(out, ref)
        res["kernels"]["paged_flash_decode"] = {
            "max_err": err, "compile_s": round(c_s, 2),
            "run_s": round(r_s, 4),
            "pool": [n_blocks, BLOCK, Hkv, D]}
        say(f"paged_flash_decode pool [{n_blocks},{BLOCK},{Hkv},{D}] "
            f"bf16: max_err {err:.4g} (atol {KERNEL_ATOL}), compile "
            f"{c_s:.1f}s, run {r_s * 1e3:.1f} ms")
        if not err <= KERNEL_ATOL:
            fails.append(f"paged_flash_decode disagrees with the "
                         f"gathered-view reference: max_err {err}")
    else:
        say("kernels: not compiled on the cpu (Mosaic needs the chip)")

    # The slot server against the reference forward.
    t0 = time.perf_counter()
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    jax.block_until_ready(params)
    res["init_s"] = round(time.perf_counter() - t0, 1)
    srv = PagedSlotServer(params, cfg, n_slots=n_slots, n_blocks=n_blocks,
                          block_size=BLOCK, prefix_cache=True)
    seen = []                   # the logits the server sampled from
    pick = srv._sampler.pick
    srv._sampler.pick = lambda lg: (seen.append(lg), pick(lg))[1]
    prompt = jnp.asarray(prompt_of(1, LONG_PROMPT, cfg.vocab_size),
                         jnp.int32)
    t0 = time.perf_counter()
    slot = srv.admit(prompt)
    t1 = time.perf_counter()
    tok1 = int(srv.last_token[slot, 0])
    out = srv.step()
    t2 = time.perf_counter()
    tok2 = out[slot]
    res["admit_s"] = round(t1 - t0, 1)      # prefill compile inside
    res["step_s"] = round(t2 - t1, 1)       # decode compile inside
    if on_chip:
        # Which path each dispatch took, read off the server's own jitted
        # functions at the shapes just driven.
        row = tf.init_cache(cfg, 1, pad)
        n_pf = srv._prefill.lower(
            params, jnp.zeros((1, pad), jnp.int32), cache=row,
            pos_offset=0).as_text().count("tpu_custom_call")
        c = srv.cache
        n_dc = srv._decode.lower(
            params, srv.last_token, c.pool_k, c.pool_v, c.block_table,
            c.lengths, srv._active_dev,
            np.full((c.n_slots, 1), -1, np.int32),  # the tick's growth
            pool_k_scale=None,
            pool_v_scale=None).as_text().count("tpu_custom_call")
        res["mosaic_calls"] = {"prefill": n_pf, "decode": n_dc}
        say(f"Mosaic custom calls in the server's lowered programs: "
            f"prefill {n_pf}, decode {n_dc}")
        if not n_pf:
            fails.append("the server's prefill did not dispatch to "
                         "flash_attention")
        if not n_dc:
            fails.append("the server's decode did not dispatch to "
                         "paged_flash_decode")
    toks = jnp.concatenate([prompt, jnp.asarray([tok1], jnp.int32)])
    with jax.default_matmul_precision("highest"):
        ref_logits, _ = jax.jit(lambda p, t: tf.forward(
            p, t, cfg, attn_impl="reference"))(params, toks[None, :])
    ref_logits = jax.block_until_ready(ref_logits[0])
    # Logit tolerance: both sides keep the residual stream in the config
    # dtype, so they differ by rounding in a different order (padded
    # flash prefill and paged decode against one exact-length causal
    # pass). bf16 rounds to 2^-8 relative about twice per layer; over
    # 18 layers that random walk is at most ~1.5% of the logits' scale,
    # which is the bound (the v5e measured 0.40%). A wrong mask, offset
    # or block index shows as O(1), and a lower precision than bf16
    # would not fit. The f32 rehearsal holds 1e-3.
    rel_tol = 1.5e-2 if cfg.dtype == jnp.bfloat16 else 1e-3
    scale = float(jnp.max(jnp.abs(ref_logits)))
    for name, got, want in (
            ("prefill", seen[0][0], ref_logits[LONG_PROMPT - 1]),
            ("decode", seen[1][slot], ref_logits[LONG_PROMPT])):
        finite = bool(jnp.isfinite(got).all())
        err = max_err(got, want) / scale
        agree = int(jnp.argmax(got)) == int(jnp.argmax(want))
        res[f"logits_{name}"] = {"rel_err": err, "finite": finite,
                                 "argmax_agrees": agree,
                                 "shape": list(got.shape)}
        say(f"{name} logits vs reference forward: rel_err {err:.4g} "
            f"(tol {rel_tol}), finite={finite}, argmax agrees={agree}")
        if not finite:
            fails.append(f"{name} logits are not finite")
        if got.shape != (cfg.vocab_size,):
            fails.append(f"{name} logits shape {got.shape}")
        if not err <= rel_tol:
            fails.append(f"{name} logits disagree with the reference "
                         f"forward: rel_err {err}")
    res["tokens"] = [tok1, int(tok2)]
    ms = devs[0].memory_stats() or {}
    res["peak_hbm_bytes"] = ms.get("peak_bytes_in_use")
    res["hbm_limit_bytes"] = ms.get("bytes_limit")
    say(f"peak HBM {res['peak_hbm_bytes']} of {res['hbm_limit_bytes']} "
        f"bytes; init {res['init_s']}s, admit {res['admit_s']}s, "
        f"first step {res['step_s']}s (compiles inside)")
    print(RESULT_TAG + json.dumps(res), flush=True)
    return 1 if fails else 0


# -- the four-chip host, run by the builder --------------------------------

def host4(platform: str) -> int:
    """One engine over four chips, then four one-chip tenants at once.
    Findings only: this is a builder's probe, not the contract."""
    from tpushare.plugin.backend import FakeBackend
    from tpushare.plugin.topology import tpu_env_for_chips
    topo = FakeBackend(chips=4, hbm_gib=16, mesh=(2, 2, 1)).probe()

    def served(d, name, vocab, prompts, mesh=None, count=1):
        """Banner, completions in flight together, /stats, drain."""
        banner = d.wait_banner(900)
        rep = {"banner": banner, "startup_s": round(time.time() - d.t0, 1)}
        fails = judge_banner(banner, platform, want_count=count)
        if banner is not None and not fails:
            res = {}
            ths = [threading.Thread(
                target=lambda i=i, p=p: res.__setitem__(i, d.complete(p, 8)))
                for i, p in enumerate(prompts)]
            for t in ths:
                t.start()
            for t in ths:
                t.join(timeout=900)
            for i in range(len(prompts)):
                st, body = res.get(i, (0, "no result"))
                fails += judge_completion(f"{name}[{i}]", st, body, vocab, 8)
            st, stats = d.request("GET", "/stats", timeout_s=30)
            fails += (judge_stats(stats, mesh=mesh) if st == 200
                      else [f"/stats: HTTP {st}: {stats}"])
        rep["failures"] = fails
        return rep

    def finish(d, rep):
        rep["drain_rc"] = d.terminate(120)
        # The startup and drain lines carry each device's bytes_in_use
        # and peak: weights spread over the mesh, not all on device 0.
        rep["lines"] = [l for l in d.output().splitlines()
                        if l.startswith(("tpushare-serve on", "drained"))]
        if rep["failures"] or rep["drain_rc"] != 0:
            rep["log_tail"] = d.output()[-2500:]

    # (1) llama3_8b over tp=4 under the four-chip grant (Gemma-2B cannot
    #     shard: n_kv_heads=1).
    grant = tpu_env_for_chips(topo, [0, 1, 2, 3])
    say(f"tp=4 grant env: {grant}")
    d = Daemon("host4_tp4", ["--preset", "llama3_8b", "--mesh", "tp=4",
                             "--platform", platform, "--port", "0"],
               child_env(platform, grant))
    vocab = VOCAB["llama3_8b"]
    try:
        tp4 = served(d, "tp4", vocab,
                     [prompt_of(1, LONG_PROMPT, vocab)]
                     + [prompt_of(10 + i, 24 + 8 * i, vocab)
                        for i in range(4)],
                     mesh={"tp": 4}, count=4)
        finish(d, tp4)
    finally:
        d.kill()
    say("tp4: " + json.dumps(tp4))

    # (2) four one-chip Gemma-2B tenants, each under the env the plugin
    #     gives for its chip, all alive at once.
    vocab = VOCAB["gemma_2b"]
    say(f"tenant 0 env: {tpu_env_for_chips(topo, [0])}")
    ds = [Daemon(f"host4_tenant{i}", [
        "--preset", "gemma_2b", "--platform", platform, "--port", "0"],
        child_env(platform, tpu_env_for_chips(topo, [i])))
        for i in range(4)]
    try:
        reps = [None] * 4
        ths = [threading.Thread(
            target=lambda i=i, d=d: reps.__setitem__(i, served(
                d, f"tenant{i}", vocab,
                [prompt_of(20 + i, LONG_PROMPT, vocab)])))
            for i, d in enumerate(ds)]
        for t in ths:               # the four chips work at the same time
            t.start()
        for t in ths:
            t.join()
        alive = [d.proc.poll() is None for d in ds]
        for d, rep in zip(ds, reps):
            finish(d, rep)
    finally:
        for d in ds:
            d.kill()
    tenants = {"all_alive_at_once": alive, "each": reps}
    say("tenants: " + json.dumps(tenants))
    with open(os.path.join(OUT_DIR, "host4.json"), "w") as f:
        json.dump({"tp4": tp4, "tenants": tenants}, f, indent=1)
    bad = (tp4["failures"] or not all(alive)
           or any(r["failures"] for r in reps))
    return 1 if bad else 0


# -- the parent ------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--host4", action="store_true")
    ap.add_argument("--child", choices=["kernels", "discovery"],
                    help=argparse.SUPPRESS)
    ap.add_argument("--preset", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--platform", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.child == "kernels":
        return kernels_child(args.preset, args.platform)
    if args.child == "discovery":
        return discovery_child()

    platform = "cpu" if args.rehearse_cpu else PASS_PLATFORM
    preset = "tiny" if args.rehearse_cpu else "gemma_2b"
    t_start = time.time()
    try:
        env = tenant_env(platform)
    except ImportError as e:
        print(f"chip_smoke: this is not a tpushare checkout ({e})",
              file=sys.stderr)
        return 2
    os.makedirs(OUT_DIR, exist_ok=True)
    if args.host4:
        rc = host4(platform)
        assert "jax" not in sys.modules, "the parent touched JAX"
        return rc
    say(f"chip_smoke: preset={preset} platform: {platform}"
        + ("  (REHEARSAL — proves nothing about the chip)"
           if args.rehearse_cpu else ""))
    say("tenant env: " + ", ".join(
        f"{k}={env[k]}" for k in sorted(env)
        if k.startswith(("TPU_", "TPUSHARE_", "ALIYUN_COM"))))
    from tpushare.utils.compile_cache import compile_cache_dir
    cache0 = cache_entries()
    say(f"compile cache: {compile_cache_dir()} ({cache0} entries)")

    # One chip-holding child at a time: each subprocess.run below has
    # returned before the next child starts.
    topo, fails = (None, []) if args.rehearse_cpu else run_discovery(env)
    kres, kfails = run_kernels_child(preset, platform, env, timeout_s=900)
    fails += kfails
    cache1 = cache_entries()
    if kres is not None:
        say("child A: " + json.dumps(
            {k: v for k, v in kres.items() if k != "failures"}))
        if topo is not None:
            fails += judge_discovery(topo, kres["device"],
                                     kres["hbm_limit_bytes"])
    # The daemon runs even when child A failed — a bring-up wants both
    # verdicts from one trip to the chip.
    dfails, drep = run_daemon(preset, platform, env, startup_s=600)
    fails += dfails
    say("child B: " + json.dumps(drep))
    banner = drep.get("banner")
    if banner and kres and (
            (banner["platform"], banner["kind"], banner["count"])
            != tuple(kres["device"][k]
                     for k in ("platform", "kind", "count"))):
        fails.append(f"the daemon's device {banner} is not child A's "
                     f"{kres['device']}")
    cache2 = cache_entries()
    say(f"compile cache entries: {cache0} at start, +{cache1 - cache0} "
        f"by child A, +{cache2 - cache1} by the daemon (JAX writes one "
        f"per compile of 1 s or more; on a warm cache those are hits)")
    say(f"wall clock: {time.time() - t_start:.0f}s")
    assert "jax" not in sys.modules, "the parent touched JAX"
    if fails:
        for f in fails:
            say(f"FAIL: {f}")
        return 1
    if platform != PASS_PLATFORM:
        say(f"rehearsal held on platform: {platform}; no pass line — "
            f"only a run on the chip can print it")
        return 3
    print(json.dumps({"ok": True, "device": kres["device"]}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
