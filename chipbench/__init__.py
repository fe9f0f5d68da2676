"""chipbench: the on-chip benchmark. See README.md in this directory."""
