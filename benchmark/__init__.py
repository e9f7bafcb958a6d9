"""tpu-mx's benchmark: BENCHMARK.json's command and everything it reads."""
