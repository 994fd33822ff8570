"""The repo's benchmark: ``python3 benchmarks/run.py`` runs one cell once.

Everything is found by the names in ``BENCHMARK.json`` (see PERF.md):

- ``configs/<config>.json``: the subject model's published keys, what was
  ``reduced`` and ``assumed``, and the ``crosscoder`` (and ``serve``) fields;
- ``traffic/<traffic>.json``: the ``runner`` and ``generator`` it uses and
  their parameters;
- ``metrics/<metric>.json``: one per-layer metric: its manifest entry (less
  the cells, which only ``BENCHMARK.json`` lists), the ``reducer`` that reads
  it and the reducer's ``args``;
- ``runners/``, ``generators/``, ``reducers/``: one module per kind, found by
  name, so a new kind is a new file;
- ``reference/``: plain float32 references; ``shapes.py``: needed operations
  and bytes; ``peaks.json``; ``attribution/*.json``: XLA module -> layer,
  joined in the order of their names;
  ``trace_reduce.py``: trace -> busy/idle, device time per layer, breakdown;
- ``tools/``: back-to-back series of runs and the two sets of six.

A later PR adds a configuration, a traffic mix, a cell, a per-layer metric or
attribution rules by adding files and manifest entries, never by editing a
file that is here.
"""
