"""The chip benchmark of this repository: ``python3 bench/run.py``.

Everything that belongs to one configuration, traffic mix, facade call
or per-layer metric is a file of its own, found by the name that
``BENCHMARK.json`` gives it:

* ``configs/<config>.json``  a deployment: generator, sizes, source;
* ``traffic/<mix>.json``     a traffic mix read by the one driver in
  ``harness.py``;
* ``calls/<call>.py``        how a facade call is made and checked
  against its plain reference;
* ``metrics/<metric>.py``    a reader that returns one metric, or None
  where it finds nothing to read;
* ``data/``                  graph generators, plain references and the
  algorithm's least-bytes count, independent of the program.

A new deployment joins by new files and new ``BENCHMARK.json`` entries,
and by one edit to entries that are there.  It brings:

* ``configs/<config>.json`` with ``generator`` naming its data module;
* ``data/<gen>.py``, where the generator is new, with
  ``generate(cfg, seed)`` -> host CSR ``(indptr, indices)`` (columns
  sorted within each row, a symmetric pattern, every self loop) and
  ``TINY``, the configuration overrides that the CPU tests run it at;
* where new, ``traffic/<mix>.json``, ``calls/<call>.py`` and
  ``metrics/<metric>.py``.

In ``BENCHMARK.json`` it adds one ``configs`` entry, its ``workloads``
entries and the ``per_layer`` entries of new metrics.  The edit: each
new cell's name goes into the ``workloads`` list of every existing
per-layer metric that reads a span the cell opens (at least
``copy_ms``, ``launch_ms`` and ``round_ms``).  The tests under
``tests/`` then run every new cell, on the engine that ``engine=None``
picks on the chip for the configuration's full-size graph, which they
work out by the program's own rule: ``tests/test_bench_extend.py``
shows such an addition on a stub.
"""
