"""One reader per metric: ``read(run, name)`` returns the metric's value
from a driver's record, or None where the record holds nothing to read."""
