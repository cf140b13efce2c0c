"""Per-layer metric readers: ``<metric>.py`` holds ``read(ctx)``, which
returns the metric's value from the run's ``Context`` (``run.py``) or
None where the window gave it nothing to read."""
