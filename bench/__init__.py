"""On-chip benchmark of MonaVec: harness, configurations, traffic mixes and readers."""
