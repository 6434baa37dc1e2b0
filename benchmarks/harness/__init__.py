"""The yardstick: everything that turns one cell of ``BENCHMARK.json``
into one result line. Later PRs add files beside these and change none."""
