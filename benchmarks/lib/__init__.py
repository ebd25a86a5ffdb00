"""The yardstick: generators, references, trace reduction, roofline counts.
Nothing here imports the program under test."""
