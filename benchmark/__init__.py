"""The benchmark: the yardstick later PRs are measured with. See README.md."""
