"""The benchmark's own code: traffic, reduction, peaks, counts, references."""
