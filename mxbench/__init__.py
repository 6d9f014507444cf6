"""mxbench: the benchmark of mxnet_tpu. One command runs one cell once:

    python -m mxbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Cells, traffic mixes, configurations and per-layer metrics are files of
their own, found by the names in BENCHMARK.json (see README.md)."""
