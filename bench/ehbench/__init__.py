"""The chip benchmark's own code: generators, traffic loops, references and
the reduction from traces and counters to metrics.  It imports the
engine under test (``repro``) only to drive it."""
