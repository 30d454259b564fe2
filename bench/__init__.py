"""The chip benchmark of the fleet keep-alive simulator (see harness.py)."""
