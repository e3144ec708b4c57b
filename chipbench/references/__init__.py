"""Plain references of the step, one module per kind of traffic; a traffic
file names its module under ``"reference"``."""
