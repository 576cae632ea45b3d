"""On-chip benchmark of the TopoSZp compressor: see BENCHMARK.json and PERF.md."""
