"""repro.parallel — logical-axis sharding rules and per-device byte accounting."""
