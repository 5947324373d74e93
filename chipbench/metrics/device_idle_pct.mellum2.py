"""Share of the traced window in which no operation ran on the device."""
import metric_lib

read = metric_lib.idle_pct
