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
"""
