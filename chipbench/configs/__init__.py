"""Configurations as they are run, with their plain references."""
