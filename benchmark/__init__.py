"""The benchmark of raft_tpu's search path on the TPU.

One run measures one cell (a configuration under a traffic mix) once::

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

``BENCHMARK.json`` at the root of the checkout names the cells, the
configurations and the metrics. Everything that belongs to one of them is
a file of its own, found by name:

* ``benchmark/configs/<config>.json``: a deployment (data shape, index and
  search parameters, the entry a user calls, the limits of ``correct``);
* ``benchmark/entries/<entry>.py``: how that entry is built and called;
* ``benchmark/traffic/<mix>.json``: a traffic mix, read by the driver
  ``benchmark/drivers/<kind>.py`` that its ``kind`` names;
* ``benchmark/metrics/<metric>.py``: the reader of one per-layer metric;
* ``benchmark/costs/<kernel>.py``: the operations and bytes a kernel's
  roofline share divides by.

A cell's ``chips`` says how many chips it spans. A one-chip cell's rows
and queries are arrays on the default device. For a cell on several
chips the harness builds ``Mesh(devices[:chips], ("shard",))``
(``harness.MESH_AXIS``, the axis name ``raft_tpu.comms`` takes by
default) and makes the rows in place sharded by row across it,
``NamedSharding(mesh, P("shard", None))``, with the queries replicated
on every chip; no chip holds more than its share of the rows. An entry
keeps the signatures ``build(cfg, x)`` and ``searcher(cfg, index, x)``
and reads the mesh from ``x.sharding.mesh``. The reference and the
control scan each chip's own rows there (:mod:`benchmark.reference`).

The yardstick lives here and nowhere in the program: the data generator
(:mod:`benchmark.data`), the exact reference (:mod:`benchmark.reference`),
the peaks (:mod:`benchmark.peaks`) and the trace reduction
(:mod:`benchmark.trace`). From raft_tpu the benchmark takes only the
entry points a user calls, and its ``obs`` counters.
"""
