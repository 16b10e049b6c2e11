"""Data sides of the row representations: one module per representation,
``bench/rows/<name>.py``, found by the name a configuration gives under
``rows`` (absent: ``dense``).

A data side imports nothing of the program and defines

* ``make_plan(cfg, mix, seed, n)``: the plan of arrivals ``[0, n)`` from the
  seed; it has at least ``n``, ``tenant``, ``starts`` and ``anchored``
  (:func:`bench.gen.schedule` makes the shared part);
* ``block_rows(cfg)``: rows made per call, for the prefill, the pool and
  the reference alike;
* ``host_rows(plan, lo, hi, block)``: the rows of arrivals ``[lo, hi)`` on
  the host, remade from the seed by index, in the layout that the service
  side of the same name slices;
* ``reference_pairs(plan, cfg, rows, admitted, block, n_query,
  devices=None)``: the plain reference, ``{g: {j: score}}`` for each query
  row ``g``, as :func:`bench.reference.compare` takes it.
"""
