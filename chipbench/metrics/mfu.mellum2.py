"""Model FLOPs of the traced window's rounds over the chips' bf16 peak."""
import metric_lib

read = metric_lib.mfu
