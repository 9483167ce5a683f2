"""The benchmark's harness: everything that measures lives under benchmarks/,
where a program PR cannot change it.  From the program it takes only the
system under test (`spark_rapids_tpu`) and its counters."""
