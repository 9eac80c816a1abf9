"""Per-layer metric readers, one small module each, found by the ``reader``
name in ``benchmark/layers/<metric>.json``.  ``read(facts, **args)`` takes the
metric from the kind's facts (counters, records, host timings) or from the
reduced trace under ``facts["trace"]``; a reader that finds nothing to read
returns None, and the harness leaves the metric out of the line."""
