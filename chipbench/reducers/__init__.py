"""Reducers of per-layer metrics, one file each, found by the ``reducer``
name in a metric's file under ``chipbench/layer_metrics/``. Each exposes

    reduce(ctx, selector) -> float | None

``ctx`` is what the traced run gathered: ``window`` (the records of the
requests due in the window), ``spans`` ({span name: [dur_ms]} from
``/trace``), ``delta`` (``/metrics`` at the window's end minus its
start), ``device`` (the reduced device trace, or None) and ``peaks``. A reducer that finds nothing to read
returns None, and the harness leaves the metric out of the line."""
