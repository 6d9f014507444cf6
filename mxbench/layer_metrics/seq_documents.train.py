"""Input pipeline: the number of documents a packed sequence held in
the last step, mean over the batch: the program's gauge
``mx_seq_documents{block}``, published from the model's
``seq_documents`` auxiliary state after the window
(``gluon.model_zoo.granite_hybrid.publish_seq_documents``). 1.0 means
the document ids never reached the model (every row one document).
Nothing on a program without the gauge."""
UNIT = "count"
GAUGE = "mx_seq_documents"


def read(run):
    from mxnet_tpu import telemetry
    values = [value for key, value in telemetry.snapshot()["gauges"].items()
              if telemetry.parse_metric_key(key)[0] == GAUGE]
    return sum(values) / len(values) if values else None
