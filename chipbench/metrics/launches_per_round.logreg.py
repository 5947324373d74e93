"""Device program executions in the traced window per round completed."""
import metric_lib

read = metric_lib.launches_per_round
