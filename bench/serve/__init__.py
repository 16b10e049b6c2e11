"""Service sides of the row representations: one module per representation,
``bench/serve/<name>.py``, beside the data side ``bench/rows/<name>.py`` of
the same name.  The one part of the benchmark that calls the program.

A service side defines

* ``build_service(cfg, gate=None, mesh=None)``: the service the
  configuration describes (``gate`` forces the strip gate on or off);
* ``install(svc, plan, n_rows, block)``: arrivals ``[0, n_rows)`` of the
  plan in the service's ring and bookkeeping, as if each had been
  submitted and flushed;
* ``submit_args(rows, a, b)``: the arguments of ``svc.submit`` between the
  tenant and the timestamps, for rows ``[a, b)`` of what the data side's
  ``host_rows`` returned.
"""
