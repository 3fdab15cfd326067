"""The benchmark's yardstick: deployment, load generator, statistics, trace
reduction and peaks.  Nothing in this package names a cell, a query or a
metric — those are files found by the names in BENCHMARK.json."""
