"""The benchmark harness: everything between ``BENCHMARK.json`` and the
simulator's entry points.

Modules, in the order a run uses them:

* :mod:`.manifest` — reads ``BENCHMARK.json`` and finds each cell's
  configuration, traffic and per-layer metric files by name;
* :mod:`.device` — names the accelerator and refuses to run without one;
* :mod:`.traffic` — the seeded request generator and the arrival feed
  the program reads;
* :mod:`.cells` — wires a configuration and a traffic mix into the
  program's own entry points;
* :mod:`.window` — the measured window: host timestamps per step, the
  step sample the reference checks;
* :mod:`.layers` — per-layer host self time, in traced runs only;
* :mod:`.profile` — device busy and idle time from a profiler trace;
* :mod:`.reference` — the plain reference that decides ``correct``;
* :mod:`.runner` — one run, end to end.
"""
