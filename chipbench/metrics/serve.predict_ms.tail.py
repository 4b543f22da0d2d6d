"""serve.predict_ms.tail: time in the router's RTT predictions
(route/predict: the prediction plane, or the knowledge base where no
predictor is trained) per MorpheusRouter.route call, in ms.

The record holds no spans of the served path, so this reads the span
totals of the whole process (``repro.core.telemetry.snapshot``) once the
run has ended; readers run in the run's process.  A run routes nothing
before its window, so they cover every route call of the window: those
after the trace's stop too, which ``serve.route_ms.tail`` leaves out,
and the retries that ``drain`` routes again."""


def read(record):
    if "rtt_s" not in record:
        return None
    try:
        from repro.core.telemetry import snapshot
    except ImportError:                   # a program without spans
        return None
    from chipbench.spans import per_route_ms
    return per_route_ms(snapshot(), "predict")
