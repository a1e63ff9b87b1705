"""Shared yardstick of the benchmark: the table of peaks, operation and
byte counts, the trace reduction, device information, state checksums,
seeded inputs and the window loop.  Nothing here imports the program
(``repro``) except :mod:`bench.common.program`, the one seam through which
the harness drives the system under test."""
