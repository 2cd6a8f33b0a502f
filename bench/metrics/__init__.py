"""One reader per metric family, found by the metric's name before the
first dot. ``read(name, record)`` returns the metric's value, or None where
the run's record holds nothing for it."""
