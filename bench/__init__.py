"""The benchmark of the CE-CoLLM serving system (see ``BENCHMARK.json``)."""
