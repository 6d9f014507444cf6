"""Kernels: the mean number of keys a query attended in the last step,
over the selector layers: the program's gauge
``mx_attn_keys_per_query{block}``, published from the layers'
``dsa_state`` auxiliary states after the window
(``gluon.model_zoo.keye_vl.publish_selector_state``). By the
mathematics ``sum_t min(t + 1, top_k) / length`` (1,792.1 at 8,192 with
top-k 2,048); the causal mean, 4,096.5, if selection ever stops
engaging. Nothing on a program without the gauge."""
UNIT = "count"
GAUGE = "mx_attn_keys_per_query"


def read(run):
    from mxnet_tpu import telemetry
    values = [value for key, value in telemetry.snapshot()["gauges"].items()
              if telemetry.parse_metric_key(key)[0] == GAUGE]
    return sum(values) / len(values) if values else None
