# TPU Pallas kernels: merge (compaction), bloom (point lookups).
