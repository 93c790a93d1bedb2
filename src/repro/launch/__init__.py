"""Launch layer: the forecast-serving driver (``python -m repro.launch.serve``,
the CLI over ``repro.serving``, docs/serving.md)."""
