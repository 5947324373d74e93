"""Share of the traced window's busy device time in which a collective
(all-to-all, all-gather, all-reduce, reduce-scatter, collective-permute)
ran, per chip and averaged over the chips."""
import metric_lib

read = metric_lib.collective_pct
