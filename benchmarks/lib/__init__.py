"""The yardstick's own arithmetic: copies of what the program also computes,
kept here so that a later PR can change the program and not the measure."""
