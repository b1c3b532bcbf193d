"""Seeded graph generators and plain references, written for the
benchmark alone: nothing here imports the program under test."""
