"""The benchmark's yardstick: traffic, weights, work counts, trace reduction."""
