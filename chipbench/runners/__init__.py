"""Traffic runners: one module per kind of traffic, named by the traffic file."""
