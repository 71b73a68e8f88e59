"""Per-layer metrics: ``<metric>.json`` names a reader module and its
arguments; ``<reader>.py`` exports ``read(run, **args)``."""
