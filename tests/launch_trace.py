"""What a slot server's tick makes the device do before its launch,
read off a CPU ``jax.profiler`` session the way the benchmark reads
``slot.programs_per_tick`` off the chip's: every program the host hands
the runtime is an event on the calling thread, whether it went through
Python or through jit's C++ fast path, which no patch of a Python
function sees (an eager ``x.at[i].set(v)`` is eight such programs).

    with launch_trace.Session() as ticks:
        with ticks.tick("crossing"):
            srv.step()
    ticks["crossing"] == {"programs": ["paged_decode"], "uploads": 0,
                          "arguments": 1}

A tick's reading covers its ``tick`` span's start to the end of the
``tpushare.slot.launch`` span inside it:

- ``programs``: the names of the programs executed, in order
  (``PjitFunction(<name>)`` calls that reached the runtime);
- ``uploads``: explicit host-to-device copies made outside any program
  call (``jnp.asarray`` and friends of a numpy array);
- ``arguments``: host values uploaded inside a program's call (its
  numpy arguments): on the chip each is a copy the launch waits for.

Where the tick is a pass of the engine (``eng._loop_once()``: it holds a
``tpushare.engine.dispatch`` span) the reading also says where the
pass's device-to-host transfer lies:

- ``fetches_in_dispatch``: transfers to the host between the START of
  the launch and the END of the ``dispatch`` stage (the sampler and the
  mirror run there): each would make the launch of tick N+1 wait for
  the device again;
- ``fetch_spans``: the ``tpushare.slot.fetch`` spans (the deferred
  fetch of the oldest owed tick) that start [before the launch, after
  the dispatch stage's end]: an engine that runs ahead reads [0, 1].

A transfer is seen where it goes through ``jax.device_get`` (the slot
servers' ``addressable_fetch``) or a scalar conversion (``int(x)``); a
bare ``np.asarray(x)`` of a device array leaves no event of its own, and
is what ``test_sync_free.count_transfers`` counts by patching it.

Run every shape once before the session: a first call compiles, and
its cache miss runs programs of its own.
"""

import glob
import tempfile

import jax
import numpy as np

#: one a program the CPU client runs
_EXECUTE = "PjRtCpuExecutable::Execute"
#: jit's C++ entry, named after the jitted function
_CALL = "PjitFunction("
#: an explicit device_put
_UPLOAD = "DevicePutWithSharding"
#: a host value among a call's arguments
_ARGUMENT = "DevicePut"
#: a device-to-host transfer on the calling thread (jax.device_get is
#: the first, then the second; int(x) and np.asarray(x) the second)
_FETCHES = ("ArrayImpl.copy_to_host_async", "np.asarray(jax.Array)")
_TICK = "launch_trace.tick:"
_LAUNCH = "tpushare.slot.launch"
_DISPATCH = "tpushare.engine.dispatch"
_FETCH_SPAN = "tpushare.slot.fetch"


class Session(dict):
    """{label: {"programs": [...], "uploads": n, "arguments": n}},
    filled on exit."""

    def __enter__(self):
        self._labels = []
        self._tmp = tempfile.TemporaryDirectory()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self._tmp.name, profiler_options=opts)
        return self

    def tick(self, label: str):
        """The span one tick runs under; a label once a session."""
        assert label not in self._labels, label
        self._labels.append(label)
        return jax.profiler.TraceAnnotation(_TICK + label)

    def __exit__(self, exc_type, exc, tb):
        try:
            jax.profiler.stop_trace()
            if exc_type is None:
                self._read()
        finally:
            self._tmp.cleanup()

    def _read(self):
        path = sorted(glob.glob(
            f"{self._tmp.name}/plugins/profile/*/*.xplane.pb"))[-1]
        data = jax.profiler.ProfileData.from_file(path)
        for plane in data.planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                       for e in line.events]
                for name, t0, t1 in evs:
                    if name.startswith(_TICK):
                        self[name[len(_TICK):]] = _reading(
                            [e for e in evs if t0 <= e[1] < t1])
        assert sorted(self) == sorted(self._labels), (
            sorted(self), self._labels)


def _reading(inside):
    launches = [e for e in inside if e[0] == _LAUNCH]
    assert len(launches) == 1, launches
    upto = launches[0][2]
    calls = sorted((e for e in inside
                    if e[0].startswith(_CALL) and e[1] < upto),
                   key=lambda e: e[1])
    programs = []
    for _, s, _e in sorted((e for e in inside
                            if e[0] == _EXECUTE and e[1] < upto),
                           key=lambda e: e[1]):
        # the innermost call around an execution names its program
        around = [c for c in calls if c[1] <= s < c[2]]
        programs.append(around[-1][0][len(_CALL):-1] if around else "?")
    reading = {"programs": programs,
               "uploads": sum(e[0] == _UPLOAD and e[1] < upto
                              for e in inside),
               "arguments": sum(e[0] == _ARGUMENT and e[1] < upto
                                for e in inside)}
    stages = [e for e in inside if e[0] == _DISPATCH]
    if stages:
        assert len(stages) == 1, stages
        launched, dispatched = launches[0][1], stages[0][2]
        spans = [e[1] for e in inside if e[0] == _FETCH_SPAN]
        reading["fetches_in_dispatch"] = sum(
            e[0] in _FETCHES and launched <= e[1] < dispatched
            for e in inside)
        reading["fetch_spans"] = [sum(t < launched for t in spans),
                                  sum(t >= dispatched for t in spans)]
    return reading


def tables_agree(srv):
    """The device block table and lengths against the host mirrors:
    what every tick of these tests must leave true."""
    np.testing.assert_array_equal(np.asarray(srv.cache.block_table),
                                  srv.cache.host_table())
    np.testing.assert_array_equal(np.asarray(srv.cache.lengths),
                                  srv.cache.host_lengths())
