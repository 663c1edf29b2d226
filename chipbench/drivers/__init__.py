"""Path drivers: build the system under test, warm it up, measure, check."""
