from repro.kernels.predicate_scan import ops, ref
from repro.kernels.predicate_scan.ops import (ScanTerm, pack_terms,
                                              predicate_scan,
                                              predicate_scan_split,
                                              predicate_scan_split_count,
                                              predicate_scan_split_ranks,
                                              compact_block, compact_rows,
                                              masked_counts)

__all__ = ["ops", "ref", "ScanTerm", "pack_terms", "predicate_scan",
           "predicate_scan_split", "predicate_scan_split_count",
           "predicate_scan_split_ranks", "compact_block", "compact_rows",
           "masked_counts"]
