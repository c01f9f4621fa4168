"""One reader per metric: ``read(run)`` returns the value, or None when
the run holds nothing to read (see ``harness.Run``)."""
