"""tpubench — the benchmark of tpushare's serving path.

Everything the yardstick needs lives under this directory (and its CPU
tests under ``tests/benchmark/``): traffic generation, the load
generator, the reduction from client stamps, ``/stats`` deltas and the
device trace to metrics, the table of peaks, the plain reference that
decides ``correct``. From the program it takes the system under test
(``ServeEngine``, ``cli.serve.serve``, ``init_params``) and nothing
else. ``README.md`` says how a later PR adds a cell as files of its own.
"""
