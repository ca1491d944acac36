"""One small reader per kind of metric. ``reduce(run, params)`` returns the
metric's value, or None when there is nothing to read (the harness then
leaves the metric out of the line). A metric file under ``metrics/`` names
its reducer module and the parameters it is called with."""
