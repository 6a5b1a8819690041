"""The chip benchmark's own code: traffic, counts, peaks, trace reduction,
the closed-loop driver and the output check.  Later changes to the
program under test (``src/repro``) cannot alter any of it."""
