"""The two drivers. Each takes everything from the cell's files."""
