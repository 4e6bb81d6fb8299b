"""SpliDT reproduction: partitioned decision trees, TPU-native."""
