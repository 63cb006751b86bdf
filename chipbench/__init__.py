"""chipbench: the benchmark of cylon_tpu on the chip (see README.md)."""
