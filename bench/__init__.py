"""The chip benchmark: cells of a model configuration under a traffic
mix, run one at a time by ``bench/run.py`` (see BENCHMARK.json)."""
